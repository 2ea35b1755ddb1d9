"""LoRA train engine + miner loop (BASELINE.json config 4).

A LoRA miner trains only low-rank adapter factors against a frozen base and
ships the *adapter pytree* over the wire — for a 7B model that is ~20 MB
instead of a ~14 GB dense delta, which is the entire reason config 4 exists.
Validators/averagers reconstruct the dense delta on their side
(models/lora.py lora_to_full_delta) and then score/merge it exactly like any
full-parameter submission; see ``fetch_delta_any``.

Protocol semantics mirror the full-param miner (engine/train.py MinerLoop):
same push/pull cadences, NaN screening before publish, and on a base-model
update the optimizer state AND the adapters reset — a fresh adapter
(b=0 -> zero effective delta) is the LoRA equivalent of the full miner
re-snapshotting its base (training_manager.py:371-377).
"""

from __future__ import annotations

import logging
from typing import Optional

import jax
import jax.numpy as jnp
import optax

from .. import delta as delta_lib
from ..models import lora as lora_lib
from ..utils import devprof
from .train import (MinerLoop, TrainEngine, TrainState, accumulated_grads,
                    _devprof_batch_bucket)

logger = logging.getLogger(__name__)


class LoRAEngine(TrainEngine):
    """Jitted adapter-only train/eval steps.

    The base is an explicit argument of the step (not a closure) so a base
    pull never recompiles, and donation applies only to the adapter state.

    Mesh semantics (config 4: a 7B frozen base does not fit one chip):
    the BASE is sharded by the same logical rules as full-param training
    (fsdp/tp over embed/qkv/mlp axes — inherited from TrainEngine), while
    the ADAPTERS and their optimizer state replicate: at rank<=64 they are
    ~0.1% of base bytes, and replicating them means the adapter all-reduce
    after the backward pass is the ONLY extra collective per step.
    """

    def __init__(self, model, lora_cfg: lora_lib.LoRAConfig, *,
                 optimizer: optax.GradientTransformation | None = None,
                 loss_fn=None, mesh=None, seq_len: int = 8,
                 accum_steps: int = 1, fused_loss: bool = False):
        # sets up tx, mesh, base param shardings, batch sharding, placement
        # helpers, and resolves fused/custom loss into _task_loss (the
        # fused path works on the EFFECTIVE params: the head is never a
        # LoRA target, so the tiled head matmul reads the frozen base head
        # — exactly the memory-constrained config-4 combination); the
        # full-param step closures it defines are shadowed below. A mesh +
        # custom loss_fn is rejected there, same as full-param training.
        super().__init__(model, optimizer=optimizer, mesh=mesh,
                         seq_len=seq_len, accum_steps=accum_steps,
                         loss_fn=loss_fn, fused_loss=fused_loss)
        self.lora_cfg = lora_cfg
        task_loss = self._task_loss

        def loss(lora_params, base, batch):
            eff = lora_lib.apply_lora(base, lora_params, lora_cfg)
            return task_loss(model, eff, batch)

        def train_step(state: TrainState, base, batch):
            l, count, grads = accumulated_grads(
                lambda p, mb: loss(p, base, mb), state.params, batch,
                accum_steps)
            with jax.named_scope("train.optimizer"):
                updates, opt_state = self.tx.update(grads, state.opt_state,
                                                    state.params)
                params = optax.apply_updates(state.params, updates)
            return (TrainState(step=state.step + 1, params=params,
                               opt_state=opt_state),
                    {"loss": l, "tokens": count})

        def eval_step(lora_params, base, batch):
            l, count = loss(lora_params, base, batch)
            return l * count, count

        # same observatory names as the full-param engine (a process
        # runs one engine; the LoRA step IS its train.step) — batch is
        # the THIRD arg here (state, base, batch)
        self.train_step = devprof.wrap(
            "train.step", jax.jit(train_step, donate_argnums=(0,)),
            bucket=lambda a, kw: _devprof_batch_bucket(a[2]))
        self.eval_step = devprof.wrap(
            "train.eval", jax.jit(eval_step),
            bucket=lambda a, kw: _devprof_batch_bucket(a[2]))

    # -- adapter placement (replicated; base placement is inherited) --------
    def _replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec())

    def place_adapters(self, adapters):
        if self.mesh is None:
            return jax.tree_util.tree_map(jnp.asarray, adapters)
        s = self._replicated()
        if self._mesh_spans_processes():
            return jax.tree_util.tree_map(
                lambda x: self._put_global(x, s), adapters)
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, s), adapters)

    def place_state_params(self, params):
        """The train state holds ADAPTERS (MinerLoop checkpoint restore)."""
        return self.place_adapters(params)

    def place_opt_state(self, opt_state):
        """Adapter optimizer state replicates like the adapters."""
        if self.mesh is None:
            return jax.tree_util.tree_map(jnp.asarray, opt_state)
        return self.place_adapters(opt_state)

    def init_state(self, rng: jax.Array, base) -> TrainState:
        return self.init_state_from(
            lora_lib.init_lora(rng, base, self.lora_cfg))

    def init_state_from(self, adapters) -> TrainState:
        """Fresh train state over an EXISTING adapter tree (val-guard
        reverts, checkpoint-less warm starts)."""
        lp = self.place_adapters(adapters)
        return TrainState(step=self.place_step(0), params=lp,
                          opt_state=jax.jit(self.tx.init)(lp))

    def abstract_state(self) -> TrainState:
        """Adapter-tree skeleton (checkpoint restore template)."""
        params_abs = jax.eval_shape(
            lambda: self.model.init_params(jax.random.PRNGKey(0)))
        adapters = jax.eval_shape(
            lambda p: lora_lib.init_lora(jax.random.PRNGKey(0), p,
                                         self.lora_cfg), params_abs)
        opt_state = jax.eval_shape(self.tx.init, adapters)
        if self.mesh is not None:
            s = self._replicated()
            attach = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                    sharding=s)
            adapters = jax.tree_util.tree_map(attach, adapters)
            opt_state = jax.tree_util.tree_map(attach, opt_state)
        return TrainState(step=jax.ShapeDtypeStruct((), jnp.int32),
                          params=adapters, opt_state=opt_state)


class LoRAMinerLoop(MinerLoop):
    """MinerLoop whose artifact is the adapter pytree.

    Reuses the full-param loop's cadences, NaN guard, metrics, and
    checkpointing; overrides what "train step", "delta", and "base reset"
    mean. ``base_params`` holds the frozen base; ``state.params`` holds the
    adapters."""

    def __init__(self, engine: LoRAEngine, transport, miner_id: str, **kw):
        if kw.get("wire_v2"):
            # adapter artifacts are already ~MB-scale and low-rank; the
            # shard-addressed top-k wire is a full-param-delta format
            raise ValueError("wire_v2 is a full-param wire format; LoRA "
                             "adapters publish their own compact form")
        super().__init__(engine, transport, miner_id, **kw)
        self._rng = jax.random.PRNGKey(0)

    # -- base lifecycle -----------------------------------------------------
    def bootstrap(self, rng: jax.Array | None = None,
                  params=None) -> None:
        """``params`` (value or zero-arg callable) seeds the frozen base when
        no base is published yet — see MinerLoop.bootstrap."""
        from .train import wire_in

        if rng is not None:
            self._rng = rng
        if self._restore_checkpoint(self._rng):
            return
        if self._multi():
            fetched = self._fetch_base_broadcast()
        elif self.transport.base_revision() is not None:
            # torn-publish guard + content-addressed pull, shared with
            # the full-param loop (engine/train.py)
            fetched = self._bootstrap_fetch_base()
        else:
            fetched = None
        if fetched is not None:
            base, rev = wire_in(self.engine, fetched[0]), fetched[1]
            self._base_revision = rev
        else:
            init = params() if callable(params) else params
            # genesis only — an eager init at the 7B config-4 scale would
            # materialize the full unsharded base on one chip
            base = init if init is not None \
                else self.engine.model.init_params(self._rng)
        # sharded placement (fsdp/tp on a mesh): the frozen base must never
        # re-transfer host->device per step, and at the 7B config-4 scale it
        # only FITS sharded
        self.base_params = self.engine.place_params(base)
        self.state = self.engine.init_state(self._rng, self.base_params)

    def _check_pull(self) -> None:
        if self._multi():
            # multi-host pod: coordinator-only transport read + broadcast,
            # identical on every process (MinerLoop._fetch_base_broadcast) —
            # per-process reads would diverge the pod's collective programs
            fetched = self._fetch_base_broadcast()
        else:
            rev = self.transport.base_revision()
            if rev is None or rev == self._base_revision:
                return
            fetched = self._fetch_base_single(rev)
        if fetched is None:
            return
        from .train import wire_in
        base, rev = wire_in(self.engine, fetched[0]), fetched[1]
        logger.info("lora miner %s: new base %s — resetting adapters + "
                    "optimizer", self.miner_id, rev and rev[:8])
        self.base_params = self.engine.place_params(base)
        self.state = self.engine.init_state(self._rng, self.base_params)
        self._base_revision = rev
        self._last_base_time = self.clock.now()
        self._reset_val_guard()
        self.report.base_pulls += 1

    # -- self-validation guard (hooks; see MinerLoop._val_guard) ------------
    def _guard_eval(self) -> float:
        """Candidate = frozen base + current adapters: the 3-arg LoRA
        eval_step already computes exactly that without materializing
        full params."""
        total = count = None
        for b in self.val_batches():
            l, c = self.engine.eval_step(self.state.params, self.base_params,
                                         self.engine.place_batch(b))
            total = l if total is None else total + l
            count = c if count is None else count + c
        if count is None or float(count) == 0:
            return float("nan")
        return float(total) / float(count)

    # -- the artifact -------------------------------------------------------
    def _build_push_snapshot(self):
        """LoRA spelling of the push snapshot program (MinerLoop hook):
        the artifact IS the adapter tree — no delta subtraction, no wire
        compression (--delta-dtype is a full-param knob) — so the program
        is wire_out + the fused finiteness screen over the adapters.
        Adapter trees mirror the base structure, so the same wire
        normalization applies: a scan_blocks LoRA miner's stacked
        [L, in, r]/[L, r, out] factors unstack to the universal per-block
        wire layout (train.py wire_out)."""
        from .train import wire_out
        engine = self.engine

        def snap(adapters):
            return wire_out(engine, adapters), delta_lib.tree_finite(adapters)

        return snap

    def _push_snapshot(self):
        return self._push_program()(self.state.params)

    # -- the loop (base is a step argument here) ----------------------------
    def _train_one(self, batch) -> dict:
        self.state, m = self.engine.train_step(
            self.state, self.base_params, self.engine.place_batch(batch))
        return m


def adapter_template(base, lora_cfg: lora_lib.LoRAConfig):
    """Host-side zeros adapter tree for payload validation — shapes come
    from ``jax.eval_shape`` so no device compute or gaussian init runs."""
    import numpy as np
    abstract = jax.eval_shape(
        lambda: lora_lib.init_lora(jax.random.PRNGKey(0), base, lora_cfg))
    return jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, s.dtype), abstract)


def _resolve_quant_template(quant_template, base):
    """int8 wire template from whatever the caller passed: a lazy+cached
    supplier (the loops), a ready tree, or None (ad-hoc callers — built
    here, a quarter-model-bytes alloc). One resolver shared by
    fetch_delta_any and densify_delta_bytes so the plain-transport and
    raw-bytes paths cannot diverge."""
    if callable(quant_template):
        return quant_template()
    if quant_template is None:
        return delta_lib.quantized_template(base)
    return quant_template


def fetch_delta_any(transport, hotkey: str, base,
                    lora_cfg: Optional[lora_lib.LoRAConfig] = None,
                    *, lora_template=None, quant_template=None,
                    accept_quant: bool = True):
    """Fetch a miner's submission as a dense delta, whatever its wire form.

    Validates against the full-param template first, then the int8
    quantized-wire template (dequantized here — downstream only ever sees
    floats), then the adapter template (reconstructing the dense delta).
    Returns None when nothing matches — the caller scores 0
    (validation_logic.py:152-166 semantics).

    This is the one-shot spelling. The validator and averager round
    paths ingest through engine/ingest.py's DeltaIngestor instead, which
    adds the per-round machinery a whole-fleet gather wants — concurrent
    fetches, a (hotkey, delta_revision) host cache that skips unchanged
    artifacts, fused cohort screening — and calls densify_delta_bytes /
    this function underneath for the actual wire-form decode.

    When the transport exposes ``fetch_delta_bytes`` the artifact is pulled
    from the network ONCE and every validation runs on the same bytes —
    the HF transport deletes its download after each fetch, so repeated
    ``fetch_delta`` calls would mean repeated full downloads per miner per
    round. Templates pass through lazily: a full-param submission never
    pays the quant/adapter template allocs; callers scoring many miners
    should pass per-base-revision cached templates.

    sparse8 submissions require the raw-bytes path: their per-leaf k
    varies with the publisher's density flag, so there is no fixed
    template to fetch against. Every shipped transport exposes
    ``fetch_delta_bytes``; a custom template-only transport scores
    sparse8 miners 0 (document that limitation to your fleet or add the
    bytes method).
    """
    fetch_bytes = getattr(transport, "fetch_delta_bytes", None)
    if fetch_bytes is not None:
        data = fetch_bytes(hotkey)
        if data is None:
            return None
        return densify_delta_bytes(data, base, lora_cfg,
                                   lora_template=lora_template,
                                   quant_template=quant_template,
                                   accept_quant=accept_quant)

    d = transport.fetch_delta(hotkey, base)
    if d is not None:
        return d
    # accept_quant=False (fleet known all-float): skip the quarter-model
    # template alloc + second transport fetch that a garbage submission
    # would otherwise pay on every call
    if accept_quant:
        quant_template = _resolve_quant_template(quant_template, base)
        q = transport.fetch_delta(hotkey, quant_template)
        if q is not None:
            # custom transports load without dtype pinning; re-check
            # host-side before trusting the bytes (int8 is the contract —
            # see densify_delta_bytes)
            if not delta_lib.shapes_match(q, quant_template,
                                          check_dtype=True, extra_dtypes=()):
                return None
            return jax.device_get(delta_lib.dequantize_delta(q))
    if lora_cfg is None:
        return None
    if lora_template is None:
        lora_template = adapter_template(base, lora_cfg)
    adapters = transport.fetch_delta(hotkey, lora_template)
    if adapters is None:
        return None
    # host-side like every other fetch result: averagers gather up to
    # ~100 densified full-param deltas before the chunked merge — a jnp
    # tree here would park each one in device HBM at ingest
    return jax.device_get(lora_lib.lora_to_full_delta(base, adapters,
                                                      lora_cfg))


def fetch_delta_any_broadcast(transport, hotkey: str, base_template,
                              lora_cfg: Optional[lora_lib.LoRAConfig] = None,
                              *, lora_template=None, quant_template=None,
                              accept_quant: bool = True):
    """Pod variant of ``fetch_delta_any``: the coordinator reads the RAW
    artifact bytes, every process receives the identical broadcast and
    densifies locally (a LoRA submission stays ~MB on the interconnect).
    ``base_template`` must be a host tree (shapes only are used)."""
    from ..parallel import multihost
    from .train import broadcast_optional_bytes, broadcast_optional_tree

    fetch_bytes = getattr(transport, "fetch_delta_bytes", None)
    if fetch_bytes is None:
        # no raw path: broadcast the densified tree (full-model-sized)
        return broadcast_optional_tree(
            base_template,
            lambda: fetch_delta_any(transport, hotkey, base_template,
                                    lora_cfg, lora_template=lora_template,
                                    quant_template=quant_template,
                                    accept_quant=accept_quant))
    data = broadcast_optional_bytes(
        fetch_bytes(hotkey) if multihost.is_coordinator() else None)
    if data is None:
        return None
    return densify_delta_bytes(data, base_template, lora_cfg,
                               lora_template=lora_template,
                               quant_template=quant_template,
                               accept_quant=accept_quant)


def densify_delta_bytes(data: bytes, base,
                        lora_cfg: Optional[lora_lib.LoRAConfig] = None,
                        *, lora_template=None, quant_template=None,
                        accept_quant: bool = True):
    """Validated artifact bytes -> dense delta (or None): the byte half of
    ``fetch_delta_any``, split out so a pod validator can broadcast the RAW
    bytes once (20 MB of adapters, not a densified full-model tree) and
    densify identically on every process.

    The try-chain discriminates the wire forms: plain dense tree, then
    int8-quantized tree ({"q","scale"} leaves), then the self-describing
    sparse8 top-k format (format marker + field-wise validation against
    the base template — k varies with the publisher's density, so it is
    not template-discriminable), then LoRA adapters. Quantized forms
    (int8 AND sparse8) are dequantized/densified here so everything
    downstream sees floats; ``accept_quant=False`` rejects both."""
    from .. import serialization as ser
    from .. import signing

    # SignedTransport verifies AND strips before bytes get here (strip is
    # then a no-op); bytes from a plain transport may still be enveloped —
    # strip unverified so an unsigned validator on a signed fleet scores
    # the payload instead of reading every submission as malformed
    try:
        data = signing.strip_envelope(data)
    except ser.PayloadError:
        return None
    # wire-v2 self-contained blob (the pod-broadcast spelling of a shard
    # manifest, serialization.pack_wire_blob): built by our own
    # coordinator AFTER its accept-wire-v2 gate, so it decodes
    # unconditionally here — magic-prefixed, so it can never be confused
    # with the msgpack forms below
    if ser.is_wire_v2_blob(data):
        return ser.unpack_wire_blob(data, base)
    try:
        return ser.validated_load(data, base)
    except ser.PayloadError:
        pass
    if accept_quant:
        quant_template = _resolve_quant_template(quant_template, base)
        try:
            # dtype-pinned: "q" MUST be int8 (a structurally matching f64
            # tree would parse at 8x the advertised bytes — see
            # validated_load)
            q = ser.validated_load(data, quant_template, check_dtypes=True)
        except ser.PayloadError:
            q = None
        if q is not None:
            return jax.device_get(delta_lib.dequantize_delta(q))
        sp = delta_lib.sparse_delta_from_bytes(data, base)
        if sp is not None:
            return sp
    if lora_cfg is None:
        return None
    if lora_template is None:
        lora_template = adapter_template(base, lora_cfg)
    try:
        adapters = ser.validated_load(data, lora_template)
    except ser.PayloadError:
        return None
    # host-side: see fetch_delta_any (averagers hold many of these at once)
    return jax.device_get(lora_lib.lora_to_full_delta(base, adapters,
                                                      lora_cfg))
