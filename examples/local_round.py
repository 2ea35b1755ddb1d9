"""One full federated round on one box, through the public API.

The reference's de-facto system test is its Local* twins running a
miner -> validator -> averager round offline (SURVEY.md §4.1); this is that
round as a minimal, readable script. Run from the repo root:

    JAX_PLATFORMS=cpu python examples/local_round.py

Everything here is the same machinery the real roles compose
(neurons/common.py) — swap InMemoryTransport/LocalChain for
HFHubTransport/BittensorChain and the code is a deployment.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributedtraining_tpu.chain import LocalChain  # noqa: E402
from distributedtraining_tpu.data import (ByteTokenizer,  # noqa: E402
                                          batch_iterator, prefetch,
                                          text_corpus)
from distributedtraining_tpu.engine import (AveragerLoop,  # noqa: E402
                                            MinerLoop, TrainEngine,
                                            Validator, WeightedAverage)
from distributedtraining_tpu.models import gpt2  # noqa: E402
from distributedtraining_tpu.transport import InMemoryTransport  # noqa: E402


def main() -> None:
    model, cfg = gpt2.make_model("tiny")
    tok = ByteTokenizer()
    train_docs = text_corpus(split="train", n_docs=48, source="synthetic")
    val_docs = text_corpus(split="val", n_docs=12, source="synthetic")

    def train_batches():
        return prefetch(batch_iterator(train_docs, tok, batch_size=4,
                                       seq_len=32, repeat=True,
                                       max_vocab=cfg.vocab_size))

    def val_batches():
        return batch_iterator(val_docs, tok, batch_size=4, seq_len=32,
                              max_vocab=cfg.vocab_size)

    transport = InMemoryTransport()
    with tempfile.TemporaryDirectory() as tmp:
        chain = LocalChain(os.path.join(tmp, "chain"), my_hotkey="hotkey_91")

        # --- miner: train, publish a weight delta --------------------------
        engine = TrainEngine(model, seq_len=32)
        miner = MinerLoop(engine, transport, "hotkey_0", send_interval=0)
        miner.bootstrap()
        report = miner.run(train_batches(), max_steps=40)
        miner.flush()
        print(f"miner  : {report.steps} steps, loss {report.last_loss:.4f}, "
              f"{report.pushes} delta pushes")

        # --- validator: score the delta, emit chain weights ----------------
        validator = Validator(TrainEngine(model, seq_len=32), transport,
                              chain, eval_batches=val_batches)
        validator.bootstrap()
        scores = validator.validate_and_score()
        nonzero = {s.hotkey: round(s.score, 5) for s in scores if s.score > 0}
        print(f"validator: base loss {validator.base_loss:.4f}, "
              f"scores {nonzero}")

        # --- averager: merge accepted deltas into a new base ---------------
        averager = AveragerLoop(TrainEngine(model, seq_len=32), transport,
                                LocalChain(os.path.join(tmp, "chain"),
                                           my_hotkey="hotkey_95"),
                                WeightedAverage(), val_batches=val_batches)
        assert averager.run_round(), "averager merged nothing"
        print(f"averager: accepted {averager.report.last_accepted}, "
              f"merged-base loss {averager.report.last_loss:.4f}")

        from distributedtraining_tpu.engine.train import host_zeros_template
        fetched = transport.fetch_base(host_zeros_template(engine))
        assert fetched is not None
        print(f"round complete: new base published (revision "
              f"{fetched[1][:12]}...)")


if __name__ == "__main__":
    main()
