"""Mean device time of ONE run of a program, in milliseconds: the events of
the device's `XLA Modules` line (one per program run, named
`jit_<function>(<fingerprint>)`) whose name matches `module_pattern`,
averaged over the device planes' runs. A run cut by an edge of the traced
window is left out: its time inside the window is not a run's time. The
program's functions carry stable names (`jit_serve_decode`,
`jit_serve_prefill`, `jit_train_step`); a program that has none matching
reads as nothing."""

import re


def read(rec, *, module_pattern: str):
    if rec.trace is None:
        return None
    rx = re.compile(module_pattern)
    lo, hi = rec.trace.window
    runs = [dur for evs in rec.trace.device_modules.values()
            for name, start, dur in evs
            if dur > 0 and start >= lo and start + dur <= hi
            and rx.search(name)]
    return sum(runs) / len(runs) / 1e6 if runs else None
