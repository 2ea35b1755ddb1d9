"""Train engine + miner loop.

TPU rebuild of the reference miner (TrainingLoop/DeltaLoop,
hivetrain/training_manager.py:28-168, 345-433):

- the train step is one jitted pure function
  ``(state, batch) -> (state, metrics)`` with donated state — params,
  optimizer update, and loss live on device; nothing crosses the host
  boundary per step except scalar metrics
- sharding-aware: given a Mesh, params/opt-state are placed by the logical
  rules (parallel/sharding.py) and the same step function runs dp/fsdp/tp
  without code changes (the reference is single-device only)
- the outer loop reproduces the reference's cadences: poll for a new base
  model every ``check_update_interval`` (ref :361-378), push the weight delta
  every ``send_interval`` seconds (ref :405-427), and — deliberately —
  reinitialize optimizer state on every base update (ref :371-377; this
  affects training dynamics and is part of the protocol's semantics)
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct

from .. import delta as delta_lib
from ..ops.losses import causal_lm_loss
from ..parallel.sharding import batch_sharding, mesh_shardings, opt_state_shardings
from ..utils import devprof, obs
from ..utils.metrics import device_metrics
from .scheduler import Clock, PeriodicAction, RealClock

logger = logging.getLogger(__name__)

Params = Any


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Params
    opt_state: Any


def default_optimizer(learning_rate: float = 5e-4,
                      *, grad_clip: float | None = None,
                      weight_decay: float = 0.01,
                      mu_dtype: str | None = None,
                      is_buffer: Callable | None = None
                      ) -> optax.GradientTransformation:
    """AdamW @ 5e-4, the reference's operating point (neurons/miner.py:121-128).
    Gradient clipping is off by default for parity (the reference has none in
    its live path) but first-class because real runs want it.

    ``mu_dtype="bfloat16"`` stores the first moment in bf16 — it halves the
    first-moment HBM footprint, which is what lets the 7B/8B full-delta
    configs keep params+AdamW resident per chip (throughput effect not
    measured).

    ``is_buffer(path) -> bool`` is the model family's rule (its config's
    ``is_buffer``, beside ``rounds_first``) for the leaves of the parameter
    tree that are no parameters: AdamW would decay them though their
    gradient is zero, so they get a zero update and no moments, and a step
    leaves them bit-equal. A family without the rule has none, and its
    optimizer is what it was."""
    tx = optax.adamw(learning_rate, weight_decay=weight_decay,
                     mu_dtype=mu_dtype)
    if grad_clip is not None:
        tx = optax.chain(optax.clip_by_global_norm(grad_clip), tx)
    if is_buffer is None:
        return tx

    def labels(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: "buffer" if is_buffer(tuple(
                getattr(k, "key", getattr(k, "name", None)) for k in path))
            else "parameter", params)

    return optax.multi_transform(
        {"parameter": tx, "buffer": optax.set_to_zero()}, labels)


def accumulated_grads(loss_fn, params, batch, accum_steps: int):
    """(loss, aux, grads) of ``loss_fn(params, batch) -> (mean, aux)``,
    gradient-accumulated over ``accum_steps`` microbatches (lax.scan).
    ``aux`` is the token count, or ``(count, counters)`` with a dict of
    what the model's layers counted; it comes back summed over the
    microbatches.

    Token-weighted across microbatches, so the result equals the full-batch
    token-mean exactly (up to float summation order): activation memory of
    batch/N at the same effective batch. With ``accum_steps == 1`` this is a
    plain value_and_grad. The batch's leading dim must divide by N."""
    if accum_steps == 1:
        (loss, aux), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch), has_aux=True)(params)
        return loss, aux, grads

    def to_micro(x):
        b = x.shape[0]
        if b % accum_steps:
            raise ValueError(
                f"batch dim {b} not divisible by accum_steps={accum_steps}")
        return x.reshape(accum_steps, b // accum_steps, *x.shape[1:])

    micro = jax.tree_util.tree_map(to_micro, batch)

    def tokens_of(aux):
        return aux[0] if isinstance(aux, tuple) else aux

    def weighted(p, mb):
        l, aux = loss_fn(p, mb)
        return l * tokens_of(aux), aux

    def body(carry, mb):
        g_acc, ls, aux_acc = carry
        (wl, aux), g = jax.value_and_grad(weighted, has_aux=True)(params, mb)
        g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
        return (g_acc, ls + wl,
                jax.tree_util.tree_map(jnp.add, aux_acc, aux)), None

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    aux0 = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda: loss_fn(
            params, jax.tree_util.tree_map(lambda x: x[0], micro))[1]))
    (g_sum, loss_sum, aux_sum), _ = jax.lax.scan(
        body, (zeros, jnp.float32(0.0), aux0), micro)
    denom = jnp.maximum(tokens_of(aux_sum), 1.0)
    grads = jax.tree_util.tree_map(
        lambda g: (g / denom).astype(g.dtype), g_sum)
    return loss_sum / denom, aux_sum, grads


def _devprof_batch_bucket(batch) -> str:
    """BxT bucket label of a token batch — the shape family XLA keys its
    compiled variants on, so the observatory's bucket matches 1:1 the
    executable actually dispatched."""
    ids = batch.get("input_ids") if isinstance(batch, dict) else None
    shape = getattr(ids, "shape", None)
    if shape is None or len(shape) < 2:
        return "-"
    return f"{shape[0]}x{shape[1]}"


def _default_lm_loss(model, params, batch, *, with_counters: bool = False):
    """(mean loss, token count). ``with_counters`` (the train step's): a
    family whose layers count something a step (a routed layer's rows
    here, rows left to other chips, its fullest expert, held rows past
    its static prefix: the model sows them, summed over layers, under
    ``intermediates/train_counters``)
    gives ``(count, counters)`` in the count's place, so that they leave
    the step beside its loss; a family that sows none gives the count
    alone, and its step is the program it was."""
    variables = {"params": params}
    kwargs = dict(attention_mask=batch.get("attention_mask"),
                  segment_ids=batch.get("segment_ids"),
                  position_ids=batch.get("position_ids"))
    if with_counters:
        logits, sown = model.apply(variables, batch["input_ids"],
                                   mutable=["intermediates"], **kwargs)
    else:
        logits, sown = model.apply(variables, batch["input_ids"],
                                   **kwargs), {}
    with jax.named_scope("train.loss"):
        loss, count = causal_lm_loss(logits, batch["input_ids"],
                                     batch.get("loss_mask"))
    counters = sown.get("intermediates", {}).get("train_counters")
    return loss, ((count, counters[0]) if counters else count)


def _fused_lm_loss(model, params, batch, impl: str = "auto", mesh=None):
    """Same contract as _default_lm_loss but the [B, T, V] logits never
    materialize: the model returns hidden states and the head matmul runs
    tile-by-tile inside fused_linear_cross_entropy (``impl`` selects the
    Pallas kernels or the portable lax.scan spelling; impl='pallas' with a
    ``mesh`` routes to the shard_map spelling). Requires a model exposing
    ``return_hidden`` with a [V, E] head param — ``lm_head`` (Llama) or
    the tied ``wte`` (GPT-2)."""
    from ..ops.losses import fused_linear_cross_entropy

    hidden = model.apply(
        {"params": params}, batch["input_ids"],
        attention_mask=batch.get("attention_mask"),
        segment_ids=batch.get("segment_ids"),
        position_ids=batch.get("position_ids"),
        return_hidden=True)
    head = params["lm_head"] if "lm_head" in params else params["wte"]
    mask = batch.get("loss_mask")
    if mesh is None:
        with jax.named_scope("train.loss"):
            return fused_linear_cross_entropy(
                hidden[:, :-1, :], head, batch["input_ids"][:, 1:],
                None if mask is None else mask[:, 1:], impl=impl)
    # mesh spelling: same math WITHOUT slicing the sequence axis — the
    # shift moves into the (tiny, global) labels/mask arrays, so hidden
    # keeps its full [B, T, E] shape and the shard_map kernel composes
    # with sp-sharded sequences (position t predicts token t+1; the last
    # column is masked out instead of sliced off)
    ids = batch["input_ids"]
    labels = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)))
    m = (jnp.ones(ids.shape[:2], jnp.float32) if mask is None
         else mask.astype(jnp.float32))
    m = jnp.pad(m[:, 1:], ((0, 0), (0, 1)))
    with jax.named_scope("train.loss"):
        return fused_linear_cross_entropy(hidden, head, labels, m,
                                          impl=impl, mesh=mesh)


class TrainEngine:
    """Owns the jitted step functions for one model + optimizer."""

    def __init__(self, model, *, optimizer: optax.GradientTransformation | None = None,
                 mesh=None, seq_len: int = 8,
                 loss_fn: Callable | None = None,
                 fused_loss: bool | str = False,
                 accum_steps: int = 1):
        """``loss_fn(model, params, batch) -> (mean_loss, count)`` overrides
        the causal-LM default — the toy classification harnesses
        (models/toy.py + ops.losses.classification_loss) plug in here. The
        jit/delta/transport facilities are task-agnostic; the *sharding*
        rules are not (they assume [B, T] token batches and LM parameter
        axes), so a mesh cannot be combined with a custom loss_fn.

        ``fused_loss=True`` swaps the built-in LM loss for the
        tiled-head variant (_fused_lm_loss) that never materializes the
        [B, T, V] logits — still the same LM task, so meshes remain
        allowed. A string value picks the implementation explicitly
        ("pallas" | "scan"; True means "auto").

        ``accum_steps=N`` splits each batch into N microbatches inside the
        jitted step (lax.scan) and applies ONE token-weighted optimizer
        update — activation memory of batch/N at the same effective batch.
        The batch's leading dim must divide by N (and the microbatch by the
        mesh's dp*fsdp). The step math is identical to the unaccumulated
        step up to summation order."""
        if mesh is not None and loss_fn is not None:
            raise ValueError(
                "mesh sharding assumes causal-LM batches ([B, T] input_ids) "
                "and LM parameter axis names; run custom-loss models "
                "unsharded (mesh=None)")
        # the PLAIN task loss (no fusion, no ambient mesh/rules): the
        # batched cohort evaluator (engine/batched_eval.py) traces this
        # inside its own vmap/shard_map programs, where a nested
        # fused-loss shard_map or an in-model sharding constraint would
        # fight the candidate-sharded spelling. Same math as the resolved
        # loss to fp tolerance (the fused CE is pinned to the dense oracle).
        self._plain_task_loss = loss_fn or _default_lm_loss
        if fused_loss:
            if loss_fn is not None:
                raise ValueError("fused_loss and a custom loss_fn are "
                                 "mutually exclusive")
            impl = fused_loss if isinstance(fused_loss, str) else "auto"
            if impl not in ("auto", "pallas", "scan"):
                # fail at construction, not minutes later inside the first
                # train_step trace
                raise ValueError(f"unknown fused_loss impl {impl!r}; "
                                 "expected True, 'auto', 'pallas' or 'scan'")
            loss_mesh = None
            if mesh is not None:
                # EVERY fused impl takes the shard_map spelling on a mesh
                # (ops/pallas_ce.fused_ce_loss_sharded: rows split across
                # dp/fsdp/sp AND tp, head all-gathered per device, totals
                # psummed — the label shift rides the global labels array,
                # so sp/ring-attention meshes compose too). The inner tile
                # engine is pallas (TPU kernels) or the portable lax scan;
                # "auto" resolves per backend. Leaving the scan spelling
                # to GSPMD instead re-materializes full-vocab buffers at
                # 8B scale (measured, scripts/scale_aot.py).
                exotic = [a for a in mesh.axis_names
                          if a not in ("dp", "fsdp", "tp", "sp")
                          and mesh.shape.get(a, 1) > 1]
                if exotic:
                    # soft fallback, not a construction-time raise: a role
                    # wired onto a research mesh (custom axis names) should
                    # run correct-but-unfused rather than refuse to boot —
                    # the fused path is a perf lever, not a semantic one
                    logger.warning(
                        "fused_loss composes with dp/fsdp/tp/sp meshes "
                        "only; mesh axes %s are unsupported — falling back "
                        "to the unfused (materialized-logits) loss", exotic)
                    fused_loss = False
                else:
                    loss_mesh = mesh
            if fused_loss:
                loss_fn = functools.partial(_fused_lm_loss, impl=impl,
                                            mesh=loss_mesh)
        self.model = model
        self.tx = optimizer or default_optimizer(is_buffer=getattr(
            getattr(model, "cfg", None), "is_buffer", None))
        self.mesh = mesh
        self._param_shardings = None
        self._batch_sharding = None
        # cached: the mesh never changes, and place_batch runs every step
        self._spans_processes = mesh is not None and any(
            d.process_index != jax.process_index()
            for d in mesh.devices.flat)
        if mesh is not None:
            self._param_shardings = mesh_shardings(model, mesh, seq_len=seq_len)
            seq_parallel = mesh.shape.get("sp", 1) > 1
            self._batch_sharding = batch_sharding(mesh,
                                                  seq_sharded=seq_parallel)
            if seq_parallel:
                # route impl="ring" attention onto this mesh's sp axis
                from ..ops.ring_attention import set_ring_mesh
                set_ring_mesh(mesh)

        base_task_loss = loss_fn or _default_lm_loss
        if mesh is not None:
            import flax.linen as nn

            from ..parallel.sharding import DEFAULT_RULES

            def task_loss(model_, params, batch, _inner=base_task_loss,
                          **kw):
                # trace with the mesh + logical-axis rules ambient so
                # in-model activation constraints
                # (nn.with_logical_constraint, models/gpt2.py) and the
                # mesh-aware embed backward (ops/embed.py) engage; inert
                # no-ops without a mesh
                with self.mesh, nn.logical_axis_rules(DEFAULT_RULES):
                    return _inner(model_, params, batch, **kw)
        else:
            task_loss = base_task_loss
        # resolved model-level loss — subclasses (LoRAEngine) reuse this so
        # fused/custom-loss resolution AND the mesh/rules activation live
        # in exactly one place
        self._task_loss = task_loss
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        self.accum_steps = accum_steps

        def loss_fn(params, batch):
            return task_loss(model, params, batch)

        # the built-in unfused loss also hands out what the model's layers
        # counted (a family that counts nothing: the same program)
        train_loss = loss_fn
        if base_task_loss is _default_lm_loss:
            def train_loss(params, batch):
                return task_loss(model, params, batch, with_counters=True)

        def train_step(state: TrainState, batch):
            loss, aux, grads = accumulated_grads(
                train_loss, state.params, batch, accum_steps)
            tokens, counters = aux if isinstance(aux, tuple) else (aux, {})
            with jax.named_scope("train.optimizer"):
                updates, opt_state = self.tx.update(grads, state.opt_state,
                                                    state.params)
                params = optax.apply_updates(state.params, updates)
            new_state = TrainState(step=state.step + 1, params=params,
                                   opt_state=opt_state)
            return new_state, {"loss": loss, "tokens": tokens, **counters}

        def eval_step(params, batch):
            loss, tokens = loss_fn(params, batch)
            return loss * tokens, tokens  # weighted for exact aggregation

        # device observatory (utils/devprof.py): per-(program, BxT-bucket)
        # cost attribution + exec histograms; single-branch pass-through
        # until devprof.enable()
        batch_bucket = _devprof_batch_bucket
        self.train_step = devprof.wrap(
            "train.step", jax.jit(train_step, donate_argnums=(0,)),
            bucket=lambda a, kw: batch_bucket(a[1]))
        self.eval_step = devprof.wrap(
            "train.eval", jax.jit(eval_step),
            bucket=lambda a, kw: batch_bucket(a[1]))

    # -- state management ---------------------------------------------------
    def init_state(self, rng: jax.Array | None = None,
                   params: Params | None = None) -> TrainState:
        """Fresh optimizer around given or newly initialized params."""
        if params is None:
            params = self.model.init_params(rng if rng is not None else jax.random.PRNGKey(0))
        # independent copy: train_step donates the state, and donated buffers
        # must never alias a tree the caller still holds (base snapshots,
        # validator bases) or those arrays get deleted underneath them
        params = jax.tree_util.tree_map(lambda x: x.copy(),
                                        self.place_params(params))
        opt_state = (jax.jit(self.tx.init)(params)  # devprof: exempt (cold init)
                     if self.mesh is None
                     else self._sharded_opt_init(params))
        return TrainState(step=self.place_step(0), params=params,
                          opt_state=opt_state)

    def place_step(self, step) -> jax.Array:
        """Step counter as a valid train-state leaf: a process-local scalar
        is not a valid jit input under multi-process SPMD, so on a
        cross-process mesh it is replicated globally (init AND checkpoint
        restore must both go through here)."""
        s = jnp.asarray(step, jnp.int32)
        if self._mesh_spans_processes():
            from jax.sharding import NamedSharding, PartitionSpec
            s = self._put_global(s, NamedSharding(self.mesh,
                                                  PartitionSpec()))
        return s

    def _mesh_spans_processes(self) -> bool:
        """True when the mesh includes devices of other processes (multi-host
        SPMD, BASELINE config 5) — host arrays must then become global
        jax.Arrays via make_array_from_* instead of plain device_put."""
        return self._spans_processes

    def _put_global(self, x, sharding):
        """Host value -> global array on a cross-process mesh. Every process
        passes the same full value (params/opt state are deterministic from
        the same seed or the same fetched base); each supplies its
        addressable shards."""
        import numpy as np
        arr = np.asarray(x)
        return jax.make_array_from_callback(arr.shape, sharding,
                                            lambda idx: arr[idx])

    def place_params(self, params: Params) -> Params:
        if self._param_shardings is None:
            return jax.tree_util.tree_map(jnp.asarray, params)
        if self._mesh_spans_processes():
            return jax.tree_util.tree_map(self._put_global, params,
                                          self._param_shardings)
        return jax.tree_util.tree_map(jax.device_put, params,
                                      self._param_shardings)

    def _sharded_opt_init(self, params):
        abstract = jax.eval_shape(self.tx.init, params)
        shardings = opt_state_shardings(abstract, self._param_shardings,
                                        self.mesh)
        return jax.jit(self.tx.init, out_shardings=shardings)(params)  # devprof: exempt (cold init)

    def abstract_params(self) -> Params:
        """Shape/dtype skeleton of the MODEL param tree (with this engine's
        shardings attached on a mesh) — the restore template for base
        snapshots. Distinct from ``abstract_state().params`` only in
        subclasses whose train state is not the model params (LoRA adapters,
        engine/lora_train.py)."""
        params = jax.eval_shape(
            lambda: self.model.init_params(jax.random.PRNGKey(0)))
        if self._param_shardings is not None:
            attach = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                       sharding=s)
            params = jax.tree_util.tree_map(attach, params,
                                            self._param_shardings)
        return params

    def abstract_state(self) -> TrainState:
        """Shape/dtype skeleton of a TrainState with zero device allocation
        (restore templates — building a concrete state just to strip it would
        briefly double peak HBM on large models). On a mesh engine the
        skeleton carries the engine's shardings so the checkpoint store
        restores directly sharded — materializing the full unsharded tree
        first would OOM exactly the models FSDP exists to fit."""
        params = self.abstract_params()
        opt_state = jax.eval_shape(self.tx.init, params)
        if self._param_shardings is not None:
            attach = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                       sharding=s)
            opt_state = jax.tree_util.tree_map(
                attach, opt_state,
                opt_state_shardings(opt_state, self._param_shardings,
                                    self.mesh))
        return TrainState(step=jax.ShapeDtypeStruct((), jnp.int32),
                          params=params, opt_state=opt_state)

    def place_state_params(self, params: Params) -> Params:
        """Placement for the TRAIN-STATE param leaves — identical to
        ``place_params`` here; the LoRA engine overrides it (its state holds
        replicated adapters while ``place_params`` shards base trees)."""
        return self.place_params(params)

    def place_opt_state(self, opt_state):
        """Re-place a restored optimizer state on this engine's mesh (restored
        arrays come back unsharded from the checkpoint store; feeding them to
        the jitted step raw would replicate full moments per device)."""
        if self.mesh is None or self._param_shardings is None:
            return jax.tree_util.tree_map(jnp.asarray, opt_state)
        abstract = jax.eval_shape(lambda x: x, opt_state)
        shardings = opt_state_shardings(abstract, self._param_shardings,
                                        self.mesh)
        if self._mesh_spans_processes():
            return jax.tree_util.tree_map(self._put_global, opt_state,
                                          shardings)
        return jax.tree_util.tree_map(jax.device_put, opt_state, shardings)

    def place_batch(self, batch: dict) -> dict:
        if self._batch_sharding is None:
            return batch
        if self._mesh_spans_processes():
            # multi-host data parallelism: each process loads its own batch
            # shard (multihost.shard_documents feeds distinct docs per host)
            # and contributes it as the addressable slice of one global batch
            import numpy as np
            return {k: jax.make_array_from_process_local_data(
                        self._batch_sharding, np.asarray(v))
                    for k, v in batch.items()}
        return {k: jax.device_put(v, self._batch_sharding)
                for k, v in batch.items()}

    # -- eval ---------------------------------------------------------------
    def evaluate(self, params: Params, batches: Iterable[dict]
                 ) -> tuple[float, float]:
        """(mean loss, perplexity) over an eval set — exact token-weighted
        aggregation across batches (ModelValidator.evaluate_model parity,
        validation_logic.py:78-97).

        Accumulation stays ON DEVICE: the validator's hot loop is
        O(miners x eval batches) calls here, and a ``float()`` per batch
        would serialize every step on a device->host round-trip. One sync at
        the end fetches both totals."""
        total = count = None
        for batch in batches:
            l, c = self.eval_step(params, self.place_batch(batch))
            total = l if total is None else total + l
            count = c if count is None else count + c
        if count is None:
            return float("nan"), float("nan")
        count_f = float(count)
        if count_f == 0:
            return float("nan"), float("nan")
        mean = float(total) / count_f
        return mean, float(jnp.exp(mean))


def broadcast_optional_tree(host_template: Params, coordinator_fetch
                            ) -> Params | None:
    """The pod's one 'optional pytree from the coordinator' protocol:
    ``coordinator_fetch()`` runs ONLY on the coordinator (may return None);
    every process returns the identical tree or the identical None. The
    collective ORDER here (ok-flag broadcast, then tree broadcast) is what
    keeps the pod in lockstep — base pulls and the validator's delta
    fetches must share this one implementation, not re-roll it."""
    import numpy as np
    from jax.experimental import multihost_utils as mhu

    from ..parallel import multihost

    t = coordinator_fetch() if multihost.is_coordinator() else None
    ok = bool(mhu.broadcast_one_to_all(np.asarray(t is not None, np.int32)))
    if not ok:
        return None
    # normalize to the TEMPLATE's dtypes: broadcast_one_to_all needs every
    # process to declare identical buffers, and only the coordinator knows
    # what the wire actually carried (e.g. a bf16 --delta-dtype submission
    # against this f32 template). Values upcast exactly; the bytes-path
    # variants keep the wire savings, this fallback trades them for the
    # collective's same-dtype contract.
    t = jax.tree_util.tree_map(
        lambda x, ref: np.asarray(jax.device_get(x)).astype(
            np.asarray(ref).dtype, copy=False),
        t if t is not None else host_template, host_template)
    return mhu.broadcast_one_to_all(t)


def broadcast_optional_bytes(data: bytes | None) -> bytes | None:
    """Bytes flavor of broadcast_optional_tree: ``data`` from the
    coordinator (None elsewhere, and None = nothing to send) becomes the
    identical bytes (or identical None) on every process. Same lockstep
    rule: one length/sentinel broadcast, then at most one payload
    broadcast — never re-roll this sequence inline."""
    import numpy as np
    from jax.experimental import multihost_utils as mhu

    from ..parallel import multihost

    if not multihost.is_coordinator():
        data = None
    n = int(mhu.broadcast_one_to_all(
        np.asarray(-1 if data is None else len(data), np.int64)))
    if n < 0:
        return None
    buf = np.zeros((n,), np.uint8)
    if data is not None:
        buf[:] = np.frombuffer(data, np.uint8)
    return np.asarray(mhu.broadcast_one_to_all(buf)).tobytes()


def mesh_spans(engine) -> bool:
    """True when the engine's mesh includes other processes' devices — the
    switch every role uses to route transport/chain reads through the
    coordinator-broadcast paths. One implementation; roles must not re-roll
    this check."""
    fn = getattr(engine, "_mesh_spans_processes", None)
    return bool(fn()) if fn is not None else False


def broadcast_metagraph(chain):
    """Round-start metagraph on a pod: the coordinator's snapshot, identical
    on every process. The hotkey list orders per-miner loops whose bodies
    contain collectives — processes syncing at different blocks could
    iterate different sets and desynchronize the pod."""
    from ..chain.base import Metagraph
    from ..parallel import multihost

    m = chain.sync() if multihost.is_coordinator() else None
    d = broadcast_json(None if m is None else
                       {"hotkeys": list(m.hotkeys), "uids": list(m.uids),
                        "stakes": list(m.stakes), "block": m.block})
    assert d is not None, "coordinator metagraph sync cannot be empty"
    return Metagraph(**d)


def broadcast_json(obj):
    """Coordinator's JSON-able value -> identical value on every process
    (consensus scores and other small chain reads)."""
    import json

    from ..parallel import multihost

    data = json.dumps(obj).encode() if multihost.is_coordinator() else None
    data = broadcast_optional_bytes(data)
    return None if data is None else json.loads(data)


def stale_submission(transport, hotkey: str, base_revision, *,
                     multi: bool) -> bool:
    """True when ``hotkey``'s delta rider names a base other than
    ``base_revision`` (the stale double-apply hazard —
    transport/base.py publish_delta_meta). Shared by Validator and
    AveragerLoop so the two roles cannot drift.

    Pod discipline: on ``multi`` EVERY process enters the broadcast
    unconditionally and only the coordinator's verdict counts — the
    averager's local ``base_revision`` is None on non-coordinators
    (CoordinatorGatedTransport.publish_base returns the revision only to
    the writer), so any locally-decided early return would diverge the
    processes at their next collective and hang the pod."""
    def local_verdict() -> bool:
        if base_revision is None:
            return False
        fm = getattr(transport, "fetch_delta_meta", None)
        if fm is None:
            return False
        try:
            meta = fm(hotkey)
        except Exception:
            return False
        if not meta:
            return False
        rev = meta.get("base_revision")
        return rev is not None and rev != base_revision

    if not multi:
        return local_verdict()
    import numpy as np
    from jax.experimental import multihost_utils as mhu

    from ..parallel import multihost
    local = local_verdict() if multihost.is_coordinator() else False
    return bool(mhu.broadcast_one_to_all(np.asarray(local, np.int32)))


def broadcast_base_fetch(transport, host_template: Params,
                         current_revision) -> tuple[Params, str | None] | None:
    """Multi-host base pull: only the coordinator reads the transport
    (per-host polls could observe different revisions mid-publish, and
    --backend local storage may not even be visible off-host); the fetched
    tree is broadcast so every process resets to IDENTICAL values at the
    identical loop point. Returns (params, rev) or None, the same on every
    process. Shared by MinerLoop, LoRAMinerLoop, and Validator."""
    import numpy as np
    from jax.experimental import multihost_utils as mhu

    def fetch():
        rev = transport.base_revision()
        if rev is None or rev == current_revision:
            return None
        fetched = transport.fetch_base(host_template)
        if fetched is None:
            return None
        # the revision rides in the broadcast as a fixed u8 leaf
        buf = np.zeros((256,), np.uint8)
        enc = (fetched[1] or "").encode()[:256]
        buf[: len(enc)] = np.frombuffer(enc, np.uint8)
        return {"params": fetched[0], "rev": buf}

    out = broadcast_optional_tree(
        {"params": host_template, "rev": np.zeros((256,), np.uint8)}, fetch)
    if out is None:
        return None
    buf = np.asarray(out["rev"])
    rev = bytes(buf[buf != 0]).decode(errors="ignore") or None
    return out["params"], rev


def host_zeros_template(engine) -> Params:
    """Host-side zeros tree of the engine's MODEL param shapes — wire
    validation / broadcast buffers with zero device allocation (an eager
    ``init_params`` here would materialize a full unsharded tree on one
    chip, which at the 7B scale is exactly the OOM the mesh exists to
    avoid)."""
    import numpy as np
    return jax.tree_util.tree_map(lambda a: np.zeros(a.shape, a.dtype),
                                  engine.abstract_params())


# -- wire layout ------------------------------------------------------------
# Artifacts (bases, full-param deltas) ALWAYS travel in the UNROLLED block
# layout (h_0..h_{L-1}); a scan_blocks run's stacked [L, ...] layout is a
# local execution detail converted at the transport boundary by the three
# helpers below. This is what makes --scan-blocks a per-role choice: a
# fleet of independent miners cannot flip an execution flag in lockstep,
# so a layout that leaked onto the wire would quarantine scan runs from
# everyone else (the round-2 advisor's finding; the loader additionally
# diagnoses a foreign stacked payload by name,
# serialization._diagnose_block_layout_mismatch).

def _scan_wire_adapters(model):
    """(model_module, n_layer) when ``model`` runs the scan layout, else
    None (unrolled models and toy models need no conversion)."""
    cfg = getattr(model, "cfg", None)
    if cfg is None or not getattr(cfg, "scan_blocks", False):
        return None
    from ..models import gpt2 as gpt2_mod
    from ..models import llama as llama_mod
    mod = llama_mod if isinstance(model, llama_mod.Llama) else gpt2_mod
    return mod, int(cfg.n_layer)


def wire_out(engine, tree: Params) -> Params:
    """Internal layout -> wire (unrolled) layout. No-op off scan_blocks."""
    ad = _scan_wire_adapters(engine.model)
    if ad is None or tree is None:
        return tree
    mod, n = ad
    return mod.unstack_blocks(tree, n)


def wire_in(engine, tree: Params) -> Params:
    """Wire (unrolled) layout -> internal layout. No-op off scan_blocks."""
    ad = _scan_wire_adapters(engine.model)
    if ad is None or tree is None:
        return tree
    mod, n = ad
    return mod.stack_blocks(tree, n)


def host_wire_template(engine) -> Params:
    """host_zeros_template in the WIRE layout — the restore template every
    transport read validates against (host numpy throughout; the unstack
    is index views, no copies)."""
    return wire_out(engine, host_zeros_template(engine))


def _snapshot(params: Params) -> Params:
    """Independent copy of a param tree. The train step donates its input
    state (in-place buffer reuse on TPU), so the miner's base snapshot must
    not alias live training params or its buffers get deleted underneath it
    (training_manager.py:349-351 does this with .clone())."""
    return jax.tree_util.tree_map(lambda x: x.copy(), params)


@dataclasses.dataclass
class MinerReport:
    steps: int = 0
    pushes: int = 0
    pushes_failed: int = 0       # publish retries exhausted (delta artifact)
    pushes_superseded: int = 0   # async pushes replaced before upload began
    base_pulls: int = 0
    val_reverts: int = 0
    last_loss: float = float("nan")


class MinerLoop:
    """The reference's DeltaLoop (training_manager.py:345-433), structured
    around injected Transport/Clock instead of globals."""

    def __init__(self, engine: TrainEngine, transport, miner_id: str, *,
                 clock: Clock | None = None,
                 send_interval: float = 800.0,        # neurons/miner.py:125
                 check_update_interval: float = 300.0,
                 metrics=None,
                 log_every: int = 1000,               # ref :394-402
                 nan_guard: bool = True,
                 delta_dtype: str | None = None,      # bf16/int8/sparse8 wire
                 delta_density: float = 1.0 / 64.0,   # sparse8 top-k density
                 wire_v2: bool = False,               # shard-addressed wire
                 wire_density: float = 1.0 / 64.0,    # v2 kept-coordinate ratio
                 wire_quant: str = "int8",            # v2 kept-value dtype
                 checkpoint_store=None,
                 checkpoint_interval: float = 600.0,
                 val_batches=None,
                 val_guard_interval: float | None = None,
                 val_guard_patience: int = 3,
                 val_guard_margin: float = 0.1,
                 keep_optimizer_on_pull: bool = False,
                 push_async: bool = False,
                 push_queue_depth: int = 1,
                 trace=None,
                 anomaly=None,
                 heartbeat=None,
                 base_fetcher=None):
        self.engine = engine
        # content-addressed base fetches (engine/basedist.BaseFetcher):
        # when set, single-host base pulls diff the published manifest
        # against the local shard store and fetch only changed-hash
        # layers (mirror racing + monolithic fallback inside). None =
        # the monolithic reference pull. Pods keep the coordinator
        # broadcast path either way.
        self.base_fetcher = base_fetcher
        # optional fleet heartbeat publisher (engine/health.py): started
        # when the loop starts (its vitals read this loop's live report),
        # final beat + close on flush(). Self-timing on its own daemon
        # thread — the step loop never polls it.
        self.heartbeat = heartbeat
        self.transport = transport
        self.miner_id = miner_id
        self.clock = clock or RealClock()
        self.metrics = metrics
        # optional bounded jax.profiler capture (utils.metrics.TraceCapture)
        self.trace = trace
        # optional anomaly-armed capture (utils.obs.AnomalyMonitor): fed
        # step times every step and loss/push counters at log boundaries;
        # a loss spike, push-failure streak, or step-time p99 blowout arms
        # its one-shot TraceCapture automatically
        self.anomaly = anomaly
        # per-push correlation-id sequence (obs.new_delta_id): stamps the
        # meta rider so validator/averager spans join to this push
        self._push_seq = 0
        self.log_every = log_every
        self.nan_guard = nan_guard
        self.delta_dtype = delta_dtype
        if not 0.0 < delta_density <= 1.0:
            # fail at construction: the first validation inside sparsify
            # happens at the first PUSH, a full send-interval of training
            # later — work a bad flag would discard
            raise ValueError(f"delta_density must be in (0, 1], "
                             f"got {delta_density}")
        self.delta_density = delta_density
        # Wire v2 (ROADMAP item 1): top-k + int8 packed per-layer form,
        # published as content-addressed shards + manifest
        # (engine/publish.py) with a miner-side error-feedback residual
        # (delta.pack_delta_v2). Orthogonal to --delta-dtype's bf16 cast
        # but mutually exclusive with the v1 compressed forms — two lossy
        # wire encodings stacked would compound rounding for no byte win.
        self.wire_v2 = wire_v2
        if wire_v2 and delta_dtype in ("int8", "sparse8"):
            raise ValueError(
                f"wire_v2 replaces the {delta_dtype!r} v1 wire format; "
                "use --wire-density/--wire-quant to tune it instead")
        if not 0.0 < wire_density <= 1.0:
            raise ValueError(f"wire_density must be in (0, 1], "
                             f"got {wire_density}")
        if wire_quant not in delta_lib.WIRE_QUANTS:
            raise ValueError(f"wire_quant must be one of "
                             f"{delta_lib.WIRE_QUANTS}, got {wire_quant!r}")
        self.wire_density = wire_density
        self.wire_quant = wire_quant
        # v2 error-feedback residual (WIRE layout, f32): the mass every
        # previous publish dropped/rounded, re-offered to the next top-k
        # selection. None until first v2 push; reset on base pulls (the
        # cumulative delta it tracks resets there).
        self._wire_residual = None
        # Reference semantics discard optimizer state on every base pull
        # (training_manager.py:371-377). ``keep_optimizer_on_pull=True``
        # carries the Adam moments across pulls instead (the standard
        # federated-practice continuation): on short merge cadences the
        # post-pull warmup transient otherwise eats most of each window's
        # progress and the fleet stops publishing once the loss curve
        # flattens (measured, scripts/soak.py). The moments were computed
        # against the pre-merge params — a mild approximation that decays
        # within a few steps and beats a cold start.
        self.keep_optimizer_on_pull = keep_optimizer_on_pull
        self.checkpoint_store = checkpoint_store
        self.report = MinerReport()
        # Async publication pipeline (engine/publish.py): the training
        # thread runs ONE jitted snapshot program and hands its non-donated
        # device outputs to a background worker; the worker pays the host
        # sync, device->host transfer, serialization, and upload. Off, the
        # SAME publisher runs inline (publish_now) — one implementation,
        # byte-identical artifacts either way.
        self.push_async = push_async
        from ..transport.retry import DEFAULT_PUBLISH_RETRY
        from .publish import DeltaPublisher
        # cap the publish retry loop's TOTAL elapsed time at the push
        # cadence: on a partitioned backend each try can block for its
        # full transport timeout, and a retry loop outliving its own
        # send interval just queues stale supersede work behind the wedge
        publish_retry = DEFAULT_PUBLISH_RETRY
        if 0 < send_interval < (publish_retry.max_elapsed or float("inf")):
            publish_retry = dataclasses.replace(publish_retry,
                                                max_elapsed=send_interval)
        self._publisher = DeltaPublisher(
            transport, miner_id, report=self.report, nan_guard=nan_guard,
            queue_depth=push_queue_depth, sleep=self.clock.sleep,
            publish_retry=publish_retry,
            wire_spec=({"format": 2, "density": wire_density,
                        "quant": wire_quant} if wire_v2 else None))
        self._push_program_cache = None
        # device-resident copy of the newest step's loss; fetched to
        # report.last_loss only at log boundaries and loop exit (a per-step
        # float() would block the host on every step's completion and
        # serialize batch prep behind device compute)
        self._last_loss_dev = None
        # what the steps since the last loss fetch counted (a routed
        # layer's rows: the step returns them beside its loss), still on
        # the device; kept only while a sink is on
        self._counted_dev: list[dict] = []
        # cached wire-layout template (shapes fixed by the model config;
        # rebuilding a full-model zeros tree per poll is O(model bytes) of
        # pure allocation — same rationale as Validator._host_template)
        self._wire_template_cache = None

        self.state: TrainState | None = None
        self.base_params: Params | None = None
        self._base_revision = None
        self._last_base_time = self.clock.now()

        # Multi-host SPMD (config 5): every cadence decision must be
        # IDENTICAL on every process — the action bodies contain collectives
        # (publish allgather, state re-placement), and per-process wall
        # clocks skew, so a locally-decided fire would desynchronize the
        # pod's programs and hang it. The coordinator's verdict is broadcast
        # at each poll site (each process polls at the same loop point).
        decide = self._synced_decision if self._multi() else None
        self._pull_action = PeriodicAction(check_update_interval,
                                           self._check_pull, self.clock,
                                           decide=decide)
        self._push_action = PeriodicAction(send_interval, self._push_delta,
                                           self.clock, decide=decide)
        # Self-validation guard (round-5 soak finding): a miner training
        # blind on a saturated task compounds an OVERFIT cumulative delta
        # against a frozen base — its train loss falls while every merge
        # candidate degrades, and the publish guard (correctly) freezes
        # the subnet. With ``val_batches`` the miner periodically scores
        # its own candidate on held-out data, keeps the best-seen params,
        # and after ``val_guard_patience`` consecutive non-improving
        # evals REVERTS to the best state (fresh optimizer — the same
        # semantics as a base pull). The published delta then tracks the
        # miner's best-known state within one eval interval instead of
        # drifting unboundedly. The reference trains blind
        # (training_manager.py:380-392 has no eval in the miner loop).
        self.val_batches = val_batches
        self.val_guard_patience = val_guard_patience
        # strikes accrue only when the candidate is WORSE than best by
        # more than this margin: a miner crawling down a flat loss curve
        # fails to beat its best on most evals from noise alone, and
        # reverting there resets Adam's moments exactly when they are
        # warming up — the guard would then pin the miner at the base
        # (measured in the first r05 soak). The r04 runaway this guard
        # exists for drifted +3.0; a 0.1 margin catches it within one
        # eval interval while tolerating plateau noise.
        self.val_guard_margin = val_guard_margin
        self._best_val: float | None = None
        # the ENTIRE TrainState at the best eval — params AND optimizer
        # moments. Reverting with a fresh optimizer (the first spelling)
        # cold-restarts Adam each time, and on the flat part of the loss
        # curve the resulting warmup transient is larger than the
        # progress a push window makes — the fleet then hovers just
        # above the published base forever (measured in the first r05
        # soak). Restoring the exact state resumes descent instead.
        # Costs one extra state copy (~3x params with AdamW).
        self._best_state: TrainState | None = None
        self._val_strikes = 0
        self._val_guard_action = None
        if val_batches is not None:
            if val_guard_patience < 1:
                raise ValueError(f"val_guard_patience must be >= 1, "
                                 f"got {val_guard_patience}")
            self._val_guard_action = PeriodicAction(
                val_guard_interval if val_guard_interval is not None
                else send_interval,
                self._val_guard, self.clock, decide=decide)
        self._last_ckpt_key = None
        self._ckpt_action = None
        if checkpoint_store is not None and self._multi():
            # orbax save is itself a collective needing a shared fs +
            # synchronized entry; the local store is not built for that
            logger.warning(
                "miner %s: local checkpointing is not supported on a "
                "multi-host mesh; disabling (restart resumes from the "
                "published base)", miner_id)
            checkpoint_store = None
            self.checkpoint_store = None
        if checkpoint_store is not None:
            self._ckpt_action = PeriodicAction(checkpoint_interval,
                                               self._save_checkpoint,
                                               self.clock)

    # -- multi-host coordination --------------------------------------------
    def _multi(self) -> bool:
        return mesh_spans(self.engine)

    def _synced_decision(self, fire: bool) -> bool:
        """Coordinator's verdict, identical on every process (collective)."""
        import numpy as np
        from jax.experimental import multihost_utils

        from ..parallel import multihost
        local = fire if multihost.is_coordinator() else False
        return bool(multihost_utils.broadcast_one_to_all(
            np.asarray(local, np.int32)))

    # -- base model lifecycle ----------------------------------------------
    def bootstrap(self, rng: jax.Array | None = None,
                  params: Params | Callable[[], Params] | None = None) -> None:
        """Resume from a local checkpoint if one exists; else pull the
        published base if one exists; else start from ``params`` (e.g. a
        pretrained checkpoint via models/convert.py, matching the
        reference's AutoModelForCausalLM.from_pretrained starting point,
        neurons/miner.py:60); else self-initialize randomly.

        ``params`` may be a zero-arg callable — it is invoked only on the
        genesis path, so a role restarting under supervision never pays the
        checkpoint load/convert for weights it immediately discards.

        The checkpoint path is strictly better than the reference's restart
        behavior (it preserves optimizer moments across a preemption); the
        base-pull path matches the reference (fresh optimizer,
        training_manager.py:371-377)."""
        if self._restore_checkpoint(rng):
            if self.base_fetcher is not None and self.base_params is not None:
                # warm the shard store from the restored base: the first
                # post-restart pull then fetches only the layers the
                # fleet actually moved while this miner was down
                self.base_fetcher.seed(wire_out(self.engine,
                                                self.base_params))
            return
        if self._multi():
            # pod boot: the same coordinator-read + broadcast as _check_pull
            # — per-process reads could see different mid-publish bases (or
            # none at all off the coordinator host) and silently train the
            # pod on divergent params
            fetched = self._fetch_base_broadcast()
        elif self.transport.base_revision() is not None:
            fetched = self._bootstrap_fetch_base()
        else:
            fetched = None
        if fetched is not None:
            base, rev = fetched
            self._base_revision = rev
            self.state = self.engine.init_state(
                params=wire_in(self.engine, base))
        else:
            init = params() if callable(params) else params
            if init is None:
                # genesis only: materializing a fresh random tree is the one
                # path that cannot avoid a full init (fetches/broadcasts use
                # the zero-alloc host template instead)
                init = self.engine.model.init_params(
                    rng if rng is not None else jax.random.PRNGKey(0))
            self.state = self.engine.init_state(params=init)
        self.base_params = _snapshot(self.state.params)

    def _fetch_base_single(self, revision=None):
        """Single-host base pull: the content-addressed delta-pull when
        a :class:`~.basedist.BaseFetcher` is wired (changed-hash layers
        only, mirror racing, monolithic fallback INSIDE the fetcher),
        else the monolithic reference pull. Either way a torn or
        hostile read returns None — "no new base this poll", never a
        mid-round exception (the fetcher degrades internally; the plain
        path's transports already return None on torn bytes)."""
        if self.base_fetcher is not None:
            return self.base_fetcher.fetch(self._wire_template(),
                                           revision=revision)
        return self.transport.fetch_base(self._wire_template())

    def _bootstrap_fetch_base(self):
        """Boot-time pull of a base the transport SAYS exists. A torn
        mid-publish read (fetch returns None while base_revision() is
        non-None) must not silently fork this miner to a genesis base —
        retry briefly (publishes commit in ms), then surface an OSError
        so the role's bounded bootstrap retry treats it like the
        transport outage it is."""
        for attempt in range(3):
            fetched = self._fetch_base_single()
            if fetched is not None:
                return fetched
            try:
                if self.transport.base_revision() is None:
                    return None   # base vanished: genuinely no base
            except OSError:
                pass
            if attempt < 2:
                self.clock.sleep(0.2 * (attempt + 1))
        raise OSError("published base unreadable at bootstrap (torn "
                      "publish or partitioned backend); refusing to "
                      "fork to a genesis base")

    def _check_pull(self) -> None:
        if self._multi():
            fetched = self._fetch_base_broadcast()
        else:
            rev = self.transport.base_revision()
            if rev is None or rev == self._base_revision:
                return
            fetched = self._fetch_base_single(rev)
        if fetched is None:
            return
        params, rev = fetched
        new_params = wire_in(self.engine, params)
        if self.keep_optimizer_on_pull and self.state is not None:
            logger.info("miner %s: new base model %s — keeping optimizer "
                        "moments", self.miner_id, rev and rev[:8])
            self.state = TrainState(
                step=self.state.step,
                params=self.engine.place_params(new_params),
                opt_state=self.state.opt_state)
        else:
            logger.info("miner %s: new base model %s — resetting optimizer",
                        self.miner_id, rev and rev[:8])
            # protocol semantics: optimizer state is discarded on base
            # update (training_manager.py:371-377)
            self.state = self.engine.init_state(params=new_params)
        self.base_params = _snapshot(self.state.params)
        # new base => the cumulative delta (and therefore the v2
        # error-feedback residual tracking its unsent mass) restarts
        # from zero; carrying the old residual would re-inject mass the
        # merge already incorporated
        self._wire_residual = None
        self._base_revision = rev
        self._last_base_time = self.clock.now()
        self._reset_val_guard()
        self.report.base_pulls += 1

    def _reset_val_guard(self) -> None:
        """New base => fresh tracking (the old best was relative to the
        superseded base)."""
        self._best_val = None
        self._best_state = None
        self._val_strikes = 0

    def _guard_eval(self) -> float:
        """Held-out loss of the current candidate (hook: LoRAMinerLoop
        evaluates adapters against the frozen base instead)."""
        loss, _ = self.engine.evaluate(self.state.params, self.val_batches())
        return loss

    def _guard_snapshot(self) -> None:
        self._best_state = _snapshot(self.state)

    def _guard_revert(self) -> None:
        """Restore the exact best-seen TrainState (params + optimizer
        moments + step). The stored copy is re-copied on the way out:
        train_step donates its input state, so handing the kept tree to
        the step would free the guard's only snapshot."""
        self.state = _snapshot(self._best_state)

    def _val_guard(self) -> None:
        if self.state is None or self.val_batches is None:
            return
        import math
        loss = self._guard_eval()
        if not math.isfinite(loss):
            logger.warning("miner %s: self-eval non-finite, ignoring",
                           self.miner_id)
            return
        if self._best_val is None or loss < self._best_val:
            self._best_val = loss
            self._guard_snapshot()
            self._val_strikes = 0
        elif loss <= self._best_val + self.val_guard_margin:
            # plateau / noise band: not a new best, and it clears the
            # strike count — patience means CONSECUTIVE over-margin
            # evals, so scattered noise spikes on a long plateau can
            # never accumulate into a spurious revert
            self._val_strikes = 0
        else:
            self._val_strikes += 1
            if (self._val_strikes >= self.val_guard_patience
                    and self._best_state is not None):
                logger.info(
                    "miner %s: val loss %.4f exceeded best %.4f by more "
                    "than the %.2f margin for %d consecutive evals — "
                    "reverting to best state (params + optimizer)",
                    self.miner_id, loss, self._best_val,
                    self.val_guard_margin, self._val_strikes)
                self._guard_revert()
                self._val_strikes = 0
                self.report.val_reverts += 1
        if self.metrics:
            self.metrics.log({"self_eval_loss": loss,
                              "self_eval_best": self._best_val,
                              "val_reverts": self.report.val_reverts},
                             step=self.report.steps)

    def _wire_template(self):
        if self._wire_template_cache is None:
            self._wire_template_cache = host_wire_template(self.engine)
        return self._wire_template_cache

    def _fetch_base_broadcast(self):
        """See broadcast_base_fetch (module level, shared with Validator).
        Returns the WIRE-layout tree; callers wire_in like every other
        fetch path (one conversion level, never two)."""
        return broadcast_base_fetch(self.transport, self._wire_template(),
                                    self._base_revision)

    # -- local checkpoint/resume (checkpoint.py) ----------------------------
    # one program + one fetch for the whole-state screen (params AND
    # optimizer moments — moments can overflow a step before params do);
    # the eager two-tree has_nonfinite spelling cost two dispatches and two
    # host round-trips per save
    _state_finite = staticmethod(jax.jit(  # devprof: exempt (per-save guard, not a step program)
        lambda params, opt_state: jnp.logical_and(
            delta_lib.tree_finite(params), delta_lib.tree_finite(opt_state))))

    def _save_checkpoint(self) -> None:
        if self.checkpoint_store is None or self.state is None:
            return
        from ..checkpoint import Snapshot
        key = (int(self.state.step), self._base_revision)
        if key == self._last_ckpt_key:  # nothing new (e.g. flush right after
            return                      # a periodic save on the final step)
        finite = (self._state_finite(self.state.params, self.state.opt_state)
                  if self.nan_guard else None)
        if self.push_async and hasattr(self.checkpoint_store, "save_async"):
            # device side on THIS thread: an independent on-device copy
            # (train_step donates the live state — the worker must never
            # hold its buffers) and the screen's dispatch, both async; the
            # flag FETCH and the orbax write happen on the store's worker,
            # with the same supersede semantics as delta pushes (only the
            # newest state matters).
            snap = Snapshot(state=_snapshot(self.state),
                            base_params=self._checkpoint_base(),
                            base_revision=self._base_revision,
                            lifetime_steps=self.report.steps)

            def screened(flag=finite) -> bool:
                if flag is None or bool(jax.device_get(flag)):
                    return True
                # never persist a poisoned state: restore prefers the
                # checkpoint, so saving NaNs would wedge the miner across
                # restarts and lose the restart-recovers-from-base escape
                logger.warning("miner %s: state non-finite, not "
                               "checkpointing", self.miner_id)
                return False

            self.checkpoint_store.save_async(snap, precondition=screened)
            self._last_ckpt_key = key
            return
        if finite is not None and not bool(jax.device_get(finite)):
            logger.warning("miner %s: state non-finite, not checkpointing",
                           self.miner_id)
            return
        try:
            self.checkpoint_store.save(
                self.checkpoint_store.next_step(),
                Snapshot(state=self.state,
                         base_params=self._checkpoint_base(),
                         base_revision=self._base_revision,
                         lifetime_steps=self.report.steps))
            self._last_ckpt_key = key
        except Exception:  # a failed save must not kill training
            logger.exception("miner %s: checkpoint save failed", self.miner_id)

    def _checkpoint_base(self):
        """The base subtree to persist: None when the base is recoverable
        from the transport by revision — it is immutable between pulls, so
        re-writing it every interval is pure redundant IO (for a LoRA miner
        it is ~99.9% of the bytes: a 7B frozen base vs ~20 MB of adapters).
        Only a self-initialized genesis base (no published revision) must
        travel in the snapshot."""
        return None if self._base_revision is not None else self.base_params

    def _restore_checkpoint(self, rng) -> bool:
        if self.checkpoint_store is None:
            return False
        if self.checkpoint_store.latest_step() is None:
            return False
        from ..checkpoint import Snapshot
        abstract = self.engine.abstract_state()
        # A corrupt/partial/incompatible checkpoint (disk fault, model-config
        # change between runs) must not wedge the miner: under supervise.sh an
        # unhandled raise here crash-loops forever, defeating the
        # restart-recovers-from-base escape hatch the save path protects.
        try:
            meta = self.checkpoint_store.read_meta() or {}
            template = Snapshot(
                state=abstract,
                base_params=(self.engine.abstract_params()
                             if meta.get("has_base", True) else None),
                base_revision=None)
            snap = self.checkpoint_store.restore(template)
            if snap is None:
                return False
            base = snap.base_params
            if base is None:
                # base omitted from the snapshot (recoverable by revision):
                # it must still be AT that revision on the transport —
                # otherwise fall through to bootstrap, which pulls the new
                # base fresh (the same optimizer/adapter reset a live base
                # pull would have forced anyway)
                base = self._refetch_base(snap.base_revision)
                if base is None:
                    logger.info(
                        "miner %s: checkpoint base %s no longer published; "
                        "bootstrapping from the current base", self.miner_id,
                        (snap.base_revision or "?")[:8])
                    return False
            self.state = TrainState(
                step=self.engine.place_step(snap.state.step),
                params=self.engine.place_state_params(snap.state.params),
                opt_state=self.engine.place_opt_state(snap.state.opt_state))
            self.base_params = _snapshot(self.engine.place_params(base))
            self._base_revision = snap.base_revision
            # lifetime counter drives metrics step numbering; falling back to
            # the in-base step would replay step numbers after a resume
            self.report.steps = (snap.lifetime_steps
                                 if snap.lifetime_steps is not None
                                 else int(self.state.step))
            self._last_ckpt_key = (int(self.state.step), self._base_revision)
        except Exception:
            logger.exception(
                "miner %s: checkpoint restore failed; falling back to "
                "base pull / self-init", self.miner_id)
            self.state = None
            self.base_params = None
            self._base_revision = None
            return False
        logger.info("miner %s: resumed from checkpoint at step %d "
                    "(lifetime %d)", self.miner_id, int(self.state.step),
                    self.report.steps)
        # the published base may have moved while we were down — resuming
        # against a superseded revision would push deltas the validator
        # applies to the wrong base. The probe must not be able to crash
        # the resume: a preemption restart is exactly when the backend may
        # still be partitioned (the very outage that killed us), and under
        # supervise.sh a raise here burns the crash-loop budget against a
        # fault the periodic pull retries through on its own cadence.
        try:
            if self.transport.base_revision() not in (None,
                                                      self._base_revision):
                logger.info("miner %s: base moved while preempted, pulling",
                            self.miner_id)
                self._check_pull()
        except Exception:
            obs.count("miner.resume_probe_errors")
            logger.warning(
                "miner %s: post-resume base probe failed (transport "
                "unreachable?); training from the checkpoint — the "
                "periodic base check will pull once the backend answers",
                self.miner_id, exc_info=True)
        return True

    def _refetch_base(self, revision) -> Params | None:
        """Host-side re-pull of the snapshot's base, valid only if the
        transport still serves exactly that revision. Single-host only by
        construction: local checkpointing is disabled on cross-process
        meshes (__init__), so this never runs inside a pod's SPMD program
        where a per-process read could diverge."""
        if revision is None or self.transport.base_revision() != revision:
            return None
        fetched = self._fetch_base_single(revision)
        if fetched is None or fetched[1] != revision:
            return None
        return wire_in(self.engine, fetched[0])

    def _build_push_snapshot(self):
        """The push path's ONE device program, traced once per loop:
        ``(params, base) -> (wire_payload, finite_flag)``. Folds
        compute_delta, the finiteness screen (delta.tree_finite — no
        separate has_nonfinite dispatch + host round-trip per push), the
        wire-layout conversion, and int8/sparse8 compression into a single
        jitted dispatch (each eager op on a cross-process mesh is its own
        collective program). Outputs are NON-donated fresh buffers, so the
        async publisher can hold them across later (donating) train steps.

        Artifacts travel in the unrolled wire layout (see wire_out);
        int8/sparse8 compression runs on the WIRE tree so scales and
        top-k selections are per wire tensor (per block under
        scan_blocks, not per stacked stack). NO error feedback:
        artifacts replace each other (each push is the whole cumulative
        delta), so carrying a residual into the next push would add the
        superseded push's rounding error."""
        engine = self.engine
        mode = self.delta_dtype
        wire_dtype = None if mode in ("int8", "sparse8") else mode
        density = self.delta_density

        if self.wire_v2:
            # v2 program: ``(params, base, residual) -> (packed,
            # new_residual, finite)``. The error-feedback residual is a
            # loop-carried state threaded THROUGH the one jitted
            # dispatch — no extra program, no host round-trip; the
            # finiteness flag screens the raw delta (a diverging miner
            # must not launder NaNs through a finite-by-construction
            # int8 encoding).
            v2_density, v2_quant = self.wire_density, self.wire_quant

            def snap_v2(params, base, residual):
                d = delta_lib.compute_delta(params, base,
                                            wire_dtype=wire_dtype)
                finite = delta_lib.tree_finite(d)
                packed, new_res = delta_lib.pack_delta_v2(
                    wire_out(engine, d), density=v2_density, quant=v2_quant,
                    residual=residual)
                # a non-finite delta must not poison the loop-carried
                # residual: new_res = delta + residual - decoded carries
                # the NaN, and tree_finite screens only the raw delta, so
                # one transient divergence would contaminate every later
                # publish until the next base pull. Keep the old residual
                # when the guard verdict is bad.
                new_res = jax.tree_util.tree_map(
                    lambda nr, r: jnp.where(finite, nr, r),
                    new_res, residual)
                return packed, new_res, finite

            return snap_v2

        def snap(params, base):
            d = delta_lib.compute_delta(params, base, wire_dtype=wire_dtype)
            finite = delta_lib.tree_finite(d)
            payload = wire_out(engine, d)
            if mode == "int8":
                payload = delta_lib.quantize_delta(payload)
            elif mode == "sparse8":
                payload = delta_lib.sparsify_delta(payload, density=density)
            return payload, finite

        return snap

    def _push_program(self):
        if self._push_program_cache is None:
            self._push_program_cache = devprof.wrap(
                "push.snapshot", jax.jit(self._build_push_snapshot()))
        return self._push_program_cache

    def _wire_residual_zeros(self):
        """f32 zeros in the WIRE layout — the first push's residual (and
        the post-base-pull reset). Host numpy: jit lifts it on dispatch,
        so no eager device alloc happens here."""
        import numpy as np
        return jax.tree_util.tree_map(
            lambda x: np.zeros(np.shape(x), np.float32),
            self._wire_template())

    def _push_snapshot(self):
        """Run the snapshot program on the CURRENT state (hook: the LoRA
        loop's program takes only the adapters)."""
        if self.wire_v2:
            if self._wire_residual is None:
                self._wire_residual = self._wire_residual_zeros()
            packed, new_res, finite = self._push_program()(
                self.state.params, self.base_params, self._wire_residual)
            # non-donated outputs: holding the new residual across later
            # (donating) train steps is safe, same as the packed payload
            self._wire_residual = new_res
            return packed, finite
        return self._push_program()(self.state.params, self.base_params)

    def _push_delta(self) -> None:
        if self.state is None:
            return
        # correlation id for THIS push: tags the snapshot span here, every
        # publisher span (sync or worker thread), and the meta rider the
        # validator/averager read it back from
        self._push_seq += 1
        cid = obs.new_delta_id(self.miner_id, self._push_seq)
        with obs.span("push.snapshot", cid=cid):
            # dispatch-only duration: the jitted program runs async on
            # device; the host cost it hides shows up in push.screen /
            # push.materialize instead
            payload, finite = self._push_snapshot()
        if not self.nan_guard:
            finite = None
        if self.push_async and not self._multi():
            # device arrays go straight to the worker; the finite fetch,
            # device->host transfer, serialization, and upload all happen
            # off-thread. A still-pending older push is superseded (each
            # artifact is the whole cumulative delta — only newest matters).
            self._publisher.submit(payload, finite, self._base_revision, cid)
            return
        if self.push_async:
            # pod rule: the snapshot program above, this flag fetch, and
            # the allgather materialization of cross-process shards are
            # collectives/synced decisions — they must run here, at the
            # loop barrier, identically on every process. Only the
            # coordinator's upload itself goes to the background.
            from .publish import host_materialize
            if finite is not None and not bool(jax.device_get(finite)):
                logger.warning("miner %s: delta has non-finite values, "
                               "not pushing", self.miner_id)
                return
            self._publisher.submit(host_materialize(payload), None,
                                   self._base_revision, cid)
            return
        self._publisher.publish_now(payload, finite, self._base_revision, cid)

    # -- the loop -----------------------------------------------------------
    def _train_one(self, batch) -> dict:
        """One engine step. The LoRA loop overrides this (its step also
        takes the frozen base); everything else in run() is shared."""
        self.state, m = self.engine.train_step(
            self.state, self.engine.place_batch(batch))
        return m

    def run(self, batches: Iterable[dict], *, max_steps: int | None = None
            ) -> MinerReport:
        if self.state is None:
            self.bootstrap()
        if self.heartbeat is not None:
            self.heartbeat.start()   # idempotent across run() calls
        start_steps = self.report.steps  # max_steps bounds *this* call
        batch_iter = iter(batches)
        try:
            while True:
                # data-wait attribution: host time blocked on the input
                # pipeline pulling the NEXT batch — the third leg of the
                # step-time anatomy (host-blocked vs device vs data-wait)
                # heartbeats and fleet_report render via devprof.anatomy().
                # One observation a ``next``: the one that finds the feed
                # exhausted is a wait too.
                with obs.phase("miner.data_wait"):
                    batch = next(batch_iter, None)
                if batch is None:
                    break
                if max_steps is not None and self.report.steps - start_steps >= max_steps:
                    break
                self._pull_action.poll()
                # step-time attribution: place + DISPATCH wall time per
                # step (the host's view: once the runtime's queue is full
                # a dispatch blocks for about one device step, so the mean
                # over a long run is the step and the p50 is not). The
                # clock is read only for a sink or an anomaly monitor.
                with obs.phase("miner.step",
                               timed=self.anomaly is not None) as stepped:
                    m = self._train_one(batch)
                if self.trace is not None:
                    self.trace.tick()
                if self.anomaly is not None:
                    self.anomaly.observe_step_ms(stepped.dur_ms)
                    self.anomaly.tick()
                self.report.steps += 1
                # keep the loss on-device: train_step dispatches
                # asynchronously, so the host can prep the next batch while
                # the chip runs. The loss is a non-donated output buffer, so
                # holding the newest one across steps is safe (and only the
                # newest is retained).
                self._last_loss_dev = m["loss"]
                if len(m) > 2 and obs.enabled():
                    self._counted_dev.append(
                        {k: v for k, v in m.items()
                         if k not in ("loss", "tokens")})
                if self.metrics and self.report.steps % self.log_every == 0:
                    self.report.last_loss = self._fetch_loss()
                    if self.anomaly is not None:
                        # loss + push-failure rules run at the log cadence:
                        # the loss is already host-fetched here, so anomaly
                        # detection never adds a device sync of its own
                        self.anomaly.observe_loss(self.report.last_loss)
                        self.anomaly.observe_push_counters(
                            self.report.pushes, self.report.pushes_failed)
                    # device memory watermarks as registry gauges at the
                    # log cadence — the exporter and the heartbeat read
                    # them from the registry, not from this one record
                    from ..utils.metrics import device_memory_watermarks
                    for k, v in device_memory_watermarks().items():
                        obs.gauge(f"device.{k}", v)
                    self.metrics.log(
                        {"train_loss": self.report.last_loss,
                         "staleness_s": self.clock.now() - self._last_base_time,
                         **device_metrics()},
                        step=self.report.steps)
                    # periodic registry flush: counters + span/step
                    # histograms ride the same sink at the same cadence
                    obs.flush(self.metrics, step=self.report.steps)
                with obs.phase("miner.actions"):
                    if self._val_guard_action is not None:
                        # before push: a revert must land before publishing,
                        # so the pushed delta is never the known-degraded
                        # state
                        self._val_guard_action.poll()
                    self._push_action.poll()
                    if self._ckpt_action is not None:
                        self._ckpt_action.poll()
        finally:
            # finally: the KeyboardInterrupt shutdown path (neurons/miner.py)
            # reads report.last_loss after an exceptional exit too. On THAT
            # path a failed fetch must not replace the in-flight exception
            # (that would skip the miner's flush()); on a normal exit a
            # fetch failure is a real error and propagates. The in-flight
            # check must happen BEFORE the inner try — inside its except
            # handler, sys.exc_info() reports the fetch failure itself.
            import sys
            exiting_exceptionally = sys.exc_info()[0] is not None
            if self._last_loss_dev is not None:
                try:
                    self.report.last_loss = self._fetch_loss()
                except Exception:
                    if not exiting_exceptionally:
                        raise
                    logger.warning(
                        "miner %s: final loss fetch failed during "
                        "exceptional shutdown", self.miner_id, exc_info=True)
        return self.report

    def _fetch_loss(self) -> float:
        """The newest loss and, in the SAME fetch, what the steps since
        the last one counted, which goes to the registry under the names
        the model gave (``train.moe.rows`` ...: docs/observability.md)."""
        with obs.phase("miner.fetch_loss"):   # the loop's one wait for the chip
            loss, counted = jax.device_get((self._last_loss_dev,
                                            self._counted_dev))
        self._counted_dev = []
        for step in counted:
            for name, val in step.items():
                obs.count(name, int(val))
        return float(loss)

    def flush(self) -> None:
        """Force a delta push (and checkpoint, if configured) now, then
        DRAIN the background publication/checkpoint workers — shutdown and
        e2e round semantics are identical to the sequential path: the final
        artifact is on the wire before flush returns."""
        self._push_delta()
        self._save_checkpoint()
        self._publisher.flush()
        if self.checkpoint_store is not None:
            cs_flush = getattr(self.checkpoint_store, "flush", None)
            if cs_flush is not None:
                cs_flush()
        if self.trace is not None:
            self.trace.close()
        if self.anomaly is not None:
            self.anomaly.close()
        if self.heartbeat is not None:
            # final beat with the exit-state counters, then stop the timer
            self.heartbeat.beat_now(wait=True)
            self.heartbeat.close()
        # final registry flush: the drained publisher's worker counters and
        # the last partial log window must reach the sink before exit
        if self.metrics is not None:
            obs.flush(self.metrics, step=self.report.steps)
