"""Mesh/sharding/collectives on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributedtraining_tpu import delta
from distributedtraining_tpu.engine import TrainEngine
from distributedtraining_tpu.models import gpt2
from distributedtraining_tpu.parallel import (
    MeshConfig, best_mesh_shape, make_mesh, mesh_shardings)
from distributedtraining_tpu.parallel.collectives import psum_weighted_merge
from distributedtraining_tpu.data import ByteTokenizer, batch_iterator, text_corpus

SEQ = 32


def batches(cfg, n=6, batch=8):
    docs = text_corpus(split="train", n_docs=64, source="synthetic")
    it = batch_iterator(docs, ByteTokenizer(), batch_size=batch, seq_len=SEQ,
                        repeat=True, max_vocab=cfg.vocab_size)
    return [next(it) for _ in range(n)]


def test_make_mesh_shapes(devices):
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    assert mesh.shape == {"dp": 2, "fsdp": 2, "sp": 1, "tp": 2}
    with pytest.raises(ValueError):
        make_mesh(MeshConfig(dp=16))


def test_best_mesh_heuristic():
    assert best_mesh_shape(1) == MeshConfig()
    assert best_mesh_shape(8) == MeshConfig(dp=8)
    big = best_mesh_shape(8, model_params=8_000_000_000)
    assert big.n_devices == 8 and big.tp > 1 or big.fsdp > 1


def test_param_shardings_resolve(devices):
    model, cfg = gpt2.make_model("tiny")
    mesh = make_mesh(MeshConfig(fsdp=2, tp=4))
    sh = mesh_shardings(model, mesh)
    flat = {"/".join(str(getattr(p, "key", p)) for p in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(sh)[0]}
    wte = next(v for k, v in flat.items() if k.endswith("wte"))
    assert wte.spec == P("tp", "fsdp")  # ("vocab","embed") under the rules
    fc = next(v for k, v in flat.items() if "c_fc" in k and "kernel" in k)
    assert fc.spec == P("fsdp", "tp")   # ("embed","mlp")


@pytest.mark.parametrize("mesh_cfg", [
    MeshConfig(dp=8),
    MeshConfig(fsdp=8),
    MeshConfig(dp=2, fsdp=2, tp=2),
])
def test_sharded_training_matches_single_device(mesh_cfg, devices):
    """The same train step must produce the same losses on any mesh."""
    model, cfg = gpt2.make_model("tiny")
    bs = batches(cfg)

    ref_engine = TrainEngine(model, seq_len=SEQ)
    ref_state = ref_engine.init_state(jax.random.PRNGKey(0))
    ref_losses = []
    for b in bs:
        ref_state, m = ref_engine.train_step(ref_state, b)
        ref_losses.append(float(m["loss"]))

    mesh = make_mesh(mesh_cfg)
    engine = TrainEngine(model, mesh=mesh, seq_len=SEQ)
    state = engine.init_state(jax.random.PRNGKey(0))
    losses = []
    for b in bs:
        state, m = engine.train_step(state, engine.place_batch(b))
        losses.append(float(m["loss"]))

    np.testing.assert_allclose(losses, ref_losses, rtol=2e-3)


def test_psum_merge_matches_reference(devices):
    """ICI all-reduce merge == plain weighted merge, including with a miner
    count that doesn't divide the axis (padding path)."""
    model, cfg = gpt2.make_model("tiny")
    base = model.init_params(jax.random.PRNGKey(0), seq_len=8)
    deltas = [jax.tree_util.tree_map(
        lambda x, s=s: 0.01 * s * jnp.ones_like(x), base) for s in range(1, 6)]
    stacked = delta.stack_deltas(deltas)
    w = jnp.asarray([0.1, 0.3, 0.2, 0.25, 0.15])

    expect = delta.weighted_merge(base, stacked, w)
    mesh = make_mesh(MeshConfig(dp=8))
    got = psum_weighted_merge(base, stacked, w, mesh, axis="dp")
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(expect)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_stack_deltas_sharded_pads_and_places(devices):
    """Ingest sharding: miner axis sharded over the mesh, padded to the axis
    size, and equal to the host stack on the real entries."""
    from distributedtraining_tpu.parallel.collectives import (
        merge_axis, stack_deltas_sharded)

    model, cfg = gpt2.make_model("tiny")
    base = model.init_params(jax.random.PRNGKey(0), seq_len=8)
    deltas = [jax.tree_util.tree_map(
        lambda x, s=s: 0.01 * s * jnp.ones_like(x), base) for s in range(1, 4)]

    mesh = make_mesh(MeshConfig(dp=8))
    assert merge_axis(mesh) == "dp"
    stacked = stack_deltas_sharded(deltas, mesh, axis="dp")
    host = delta.stack_deltas(deltas)
    for s, h in zip(jax.tree_util.tree_leaves(stacked),
                    jax.tree_util.tree_leaves(host)):
        assert s.shape[0] == 8                      # padded 3 -> 8
        assert s.sharding.spec[0] == "dp"           # miner axis sharded
        np.testing.assert_array_equal(np.asarray(s[:3]), np.asarray(h))
        assert not np.asarray(s[3:]).any()          # zero padding


@pytest.mark.parametrize("strategy_name", ["weighted", "parameterized"])
def test_averager_round_on_mesh_matches_host(strategy_name, devices, tmp_path):
    """A full AveragerLoop round on a dp=8 mesh engine (ingest-sharded stack,
    psum/GSPMD all-reduce merge) publishes the same base as the host path —
    BASELINE config 3's merge, M=3 not dividing the axis (padding live)."""
    from distributedtraining_tpu.chain import LocalChain
    from distributedtraining_tpu.engine import (
        AveragerLoop, FakeClock, ParameterizedMerge, WeightedAverage)
    from distributedtraining_tpu.transport import InMemoryTransport

    model, cfg = gpt2.make_model("tiny")
    base = model.init_params(jax.random.PRNGKey(0))
    bs = batches(cfg, n=2)

    def make_strategy():
        if strategy_name == "weighted":
            return WeightedAverage()
        # sgd for host-vs-mesh PARITY: adam steps are ~lr*sign(g), so a
        # reduction-order sign flip on a near-zero meta-gradient becomes
        # a full-lr weight divergence (the round-4 on-chip lesson);
        # adam behavior itself is covered by the
        # discrimination tests in test_engines.py
        return ParameterizedMerge(model, meta_epochs=2, meta_lr=0.3,
                                  per_tensor=True, meta_optimizer="sgd")

    def run(engine):
        transport = InMemoryTransport()
        transport.publish_base(base)
        for i in range(3):
            d = jax.tree_util.tree_map(
                lambda x, s=i + 1: 0.005 * s * jnp.ones_like(x), base)
            transport.publish_delta(f"hotkey_{i}", d)
        chain = LocalChain(str(tmp_path / f"{strategy_name}-{id(engine)}"),
                           my_hotkey="hotkey_99", epoch_length=0,
                           clock=FakeClock())
        loop = AveragerLoop(engine, transport, chain, make_strategy(),
                            val_batches=lambda: bs, clock=FakeClock())
        loop.bootstrap(params=base)
        assert loop.run_round()
        assert loop.report.last_accepted == 3
        return jax.device_get(loop.base_params)

    host = run(TrainEngine(model, seq_len=SEQ))
    mesh = make_mesh(MeshConfig(dp=8))
    sharded = run(TrainEngine(model, mesh=mesh, seq_len=SEQ))
    for a, b in zip(jax.tree_util.tree_leaves(sharded),
                    jax.tree_util.tree_leaves(host)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_multihost_single_host_degradation(devices):
    """initialize() is a no-op on one host; pod_mesh spans all devices;
    shard_documents with one process yields everything."""
    from distributedtraining_tpu.parallel import multihost

    multihost.initialize()  # must not raise or start a coordinator
    assert multihost.is_coordinator()

    mesh = multihost.pod_mesh(fsdp=2, tp=2)
    assert mesh.shape["dp"] * mesh.shape["fsdp"] * mesh.shape["sp"] \
        * mesh.shape["tp"] == len(jax.devices())
    assert mesh.shape["fsdp"] == 2 and mesh.shape["tp"] == 2

    docs = list(multihost.shard_documents(["a", "b", "c"]))
    assert docs == ["a", "b", "c"]
    # explicit 2-process split: disjoint and covering
    p0 = list(multihost.shard_documents("abcdef", process_index=0,
                                        process_count=2))
    p1 = list(multihost.shard_documents("abcdef", process_index=1,
                                        process_count=2))
    assert p0 == list("ace") and p1 == list("bdf")

    import pytest as _pytest
    with _pytest.raises(ValueError):
        multihost.pod_mesh(fsdp=3)  # 8 % 3 != 0


def test_multihost_env_detection(monkeypatch):
    """The multi-process decision comes from environment signals only —
    probing jax.process_count() would initialize the XLA backend and make a
    later jax.distributed.initialize() raise unconditionally."""
    from distributedtraining_tpu.parallel import multihost

    for var in multihost._MULTIPROCESS_ENV_VARS + (
            "SLURM_NTASKS", "SLURM_NPROCS", "OMPI_COMM_WORLD_SIZE",
            "TPU_WORKER_HOSTNAMES"):
        monkeypatch.delenv(var, raising=False)
    # isolate from the /dev/accel* metadata-server fallback: on a real pod
    # slice it would answer >1 and on non-GCE hosts it would hit the network
    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "1")
    assert not multihost._multiprocess_env()

    # single-host TPU VMs set one hostname; only several workers signal a
    # pod — and one hostname DECIDES: the metadata server is never asked
    # (the chip tool's machine has no network)
    monkeypatch.delenv("TPU_SKIP_MDS_QUERY")
    monkeypatch.setattr(multihost, "_gce_tpu_worker_count",
                        lambda: pytest.fail("single host went to the "
                                            "network to ask"))
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    assert not multihost._multiprocess_env()
    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "1")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "w0,w1,w2,w3")
    assert multihost._multiprocess_env()
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES")

    monkeypatch.setenv("SLURM_NTASKS", "1")
    assert not multihost._multiprocess_env()
    monkeypatch.setenv("SLURM_NTASKS", "4")
    assert multihost._multiprocess_env()
    monkeypatch.delenv("SLURM_NTASKS")
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:1234")
    assert multihost._multiprocess_env()


def test_metadata_query_is_bounded_by_a_hard_deadline(monkeypatch):
    """A name lookup that stalls (urlopen's timeout does not bound it)
    cannot hold start-up: the query is abandoned at the deadline and the
    answer is "one host"."""
    import time
    import urllib.request

    from distributedtraining_tpu.parallel import multihost

    def stalled(*a, **kw):
        time.sleep(5.0)
        raise OSError("never answered")

    monkeypatch.setattr(urllib.request, "urlopen", stalled)
    t0 = time.monotonic()
    assert multihost._gce_tpu_worker_count(deadline_s=0.2) == 1
    assert time.monotonic() - t0 < 2.0


def test_resolve_mesh_config():
    from distributedtraining_tpu.parallel import resolve_mesh_config

    # explicit axes: dp=0 fills the remainder
    assert resolve_mesh_config(n_devices=8, fsdp=2, tp=2) == \
        MeshConfig(dp=2, fsdp=2, sp=1, tp=2)
    assert resolve_mesh_config(n_devices=8, dp=4) == MeshConfig(dp=4)
    # auto: small model -> pure dp; 8B params -> sharded axes
    assert resolve_mesh_config(n_devices=8, auto=True,
                               model_params=124_000_000) == MeshConfig(dp=8)
    big = resolve_mesh_config(n_devices=32, auto=True,
                              model_params=8_000_000_000)
    assert big.n_devices == 32 and (big.fsdp > 1 or big.tp > 1)
    # auto overrides explicit axes (documented contract of --mesh-auto)
    assert resolve_mesh_config(n_devices=8, dp=1, fsdp=8, auto=True,
                               model_params=1_000) == MeshConfig(dp=8)


def test_resolve_mesh_config_auto_with_dcn():
    from distributedtraining_tpu.parallel import resolve_mesh_config

    # auto + multi-slice: pick per granule, multiply dp — fsdp/sp/tp never
    # span a granule, so hybrid layout keeps them on ICI
    small = resolve_mesh_config(n_devices=16, auto=True, dcn_dp=2,
                                model_params=124_000_000)
    assert small == MeshConfig(dp=16)
    big = resolve_mesh_config(n_devices=32, auto=True, dcn_dp=2,
                              model_params=8_000_000_000)
    assert big.n_devices == 32
    assert big.dp % 2 == 0                 # dcn factor lives in dp
    assert big.fsdp * big.sp * big.tp <= 16  # inside one granule
    with pytest.raises(ValueError):
        resolve_mesh_config(n_devices=9, auto=True, dcn_dp=2)


def test_parameterized_mesh_merge_lowers_to_allreduce(devices):
    """The GSPMD claim at engine/average.py (_build_step): with an
    ingest-sharded miner stack, the parameterized mixture's sum over the
    miner axis must COMPILE to partial sums + an all-reduce — checked in
    the HLO text, not just numerically. This is also the regression guard
    for the closure trap _build_step documents: when base/stacked were
    closed over instead of passed as jit arguments, the stack was embedded
    as a (replicated) constant and NO collective appeared."""
    from distributedtraining_tpu.engine import ParameterizedMerge
    from distributedtraining_tpu.parallel.collectives import (
        merge_axis, stack_deltas_sharded)

    model, cfg = gpt2.make_model("tiny")
    base = model.init_params(jax.random.PRNGKey(0), seq_len=8)
    deltas = [jax.tree_util.tree_map(
        lambda x, s=s: 0.01 * s * jnp.ones_like(x), base) for s in range(1, 4)]
    mesh = make_mesh(MeshConfig(dp=8))
    stacked = stack_deltas_sharded(deltas, mesh, axis=merge_axis(mesh))

    pm = ParameterizedMerge(model, per_tensor=True)
    mixture, _, _ = pm._build_step(delta.miner_axis_size(stacked))
    w = jax.tree_util.tree_map(lambda _: jnp.zeros((3,), jnp.float32), base)
    txt = mixture.lower(w, base, stacked).compile().as_text()
    assert "all-reduce" in txt, "sharded merge compiled without an all-reduce"

    host_stack = delta.stack_deltas(deltas)
    mixture_host, _, _ = pm._build_step(delta.miner_axis_size(host_stack))
    txt_host = mixture_host.lower(
        w, base, host_stack).compile().as_text()
    assert "all-reduce" not in txt_host


def test_embed_lookup_matmul_backward(devices):
    """On dp x fsdp meshes the embedding backward takes the one-hot
    einsum spelling (no GSPMD involuntary-remat reshard of the cotangent
    — see ops/embed.py); gradients must equal the scatter spelling
    exactly, including duplicate-id accumulation, and routing must stay
    on the plain gather without an ambient dp x fsdp mesh."""
    from distributedtraining_tpu.ops import embed

    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 64, (4, 8)), jnp.int32)
    ids = ids.at[0, 0].set(ids[0, 1])  # force a duplicate (accumulation)
    ct = jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)

    assert not embed._ambient_mesh_needs_matmul_bwd()
    with make_mesh(MeshConfig(dp=2, fsdp=2, tp=2)):
        assert embed._ambient_mesh_needs_matmul_bwd()
    with make_mesh(MeshConfig(dp=8)):
        assert not embed._ambient_mesh_needs_matmul_bwd()

    take = embed._take_matmul_bwd(64, "float32")
    g_ref = jax.grad(lambda t: (jnp.take(t, ids, axis=0) * ct).sum())(table)
    g_new = jax.grad(lambda t: (take(t, ids) * ct).sum())(table)
    np.testing.assert_allclose(np.asarray(g_new), np.asarray(g_ref),
                               rtol=1e-6, atol=1e-6)
