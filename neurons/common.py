"""Shared composition for the role entry points.

The reference's neurons/{miner,validator,averager}.py each hand-assemble
dataset + tokenizer + model + HF/chain managers with copy-pasted Dataset
classes (neurons/miner.py:69-99 vs validator.py:62-93 vs averager.py:71-90).
Here composition is one function, driven by RunConfig, with no import-time
side effects.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Any, Callable, Iterable

from distributedtraining_tpu.chain import LocalAddressStore, LocalChain
from distributedtraining_tpu.config import RunConfig
from distributedtraining_tpu.data import (ByteTokenizer, batch_iterator,
                                          load_tokenizer, text_corpus)
from distributedtraining_tpu.data.datasets import shuffle_seed_for
from distributedtraining_tpu.engine import TrainEngine, default_optimizer
from distributedtraining_tpu.models import family_of
from distributedtraining_tpu.parallel import make_mesh, resolve_mesh_config
from distributedtraining_tpu.transport import (InMemoryTransport,
                                               LocalFSTransport)
from distributedtraining_tpu.utils import JSONLSink, multi_sink
from distributedtraining_tpu.utils.platform import enable_compile_cache

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class Components:
    cfg: RunConfig
    model: Any
    model_cfg: Any
    engine: TrainEngine
    transport: Any
    chain: Any
    address_store: Any
    tokenizer: Any
    metrics: Any
    lora_cfg: Any = None  # set when --lora-rank > 0 (config 4 mode)

    def train_batches(self, *, repeat: bool = True) -> Iterable[dict]:
        import jax

        docs = text_corpus(split="train", source=self.cfg.dataset,
                           n_docs=self.cfg.n_docs)
        bs = self.cfg.batch_size
        if jax.process_count() > 1:
            # --batch-size is the GLOBAL batch on a pod: each process feeds
            # its own document shard at batch_size/process_count and the
            # engine assembles one global array per step (place_batch)
            from distributedtraining_tpu.parallel import multihost
            if bs % jax.process_count():
                # silently shrinking the global batch would surface later as
                # a baffling dp-axis divisibility error in place_batch
                raise SystemExit(
                    f"--batch-size {bs} (global) must be divisible by the "
                    f"process count {jax.process_count()}")
            docs = list(multihost.shard_documents(docs))
            bs //= jax.process_count()
        # ref trains via a shuffling DataLoader (neurons/miner.py:101-106);
        # eval stays ordered; per-hotkey seed decorrelates the miners
        it = batch_iterator(docs, self.tokenizer, batch_size=bs,
                            seq_len=self.cfg.seq_len, repeat=repeat,
                            max_vocab=self.model_cfg.vocab_size,
                            shuffle=True,
                            seed=shuffle_seed_for(self.cfg.hotkey))
        if self.cfg.prefetch_depth > 0:
            from distributedtraining_tpu.data import prefetch
            it = prefetch(it, depth=self.cfg.prefetch_depth)
        return it

    def initial_params(self):
        """Pretrained starting point per --init-from (None without the flag).
        Passed to bootstrap as a thunk and invoked only on the genesis path —
        a published base or local checkpoint always wins, and a supervised
        restart must not re-pay the checkpoint load/convert for weights it
        would immediately discard (reference boot order: from_pretrained then
        pull, neurons/miner.py:60 + training_manager.py:361-378)."""
        if not self.cfg.init_from:
            return None
        from distributedtraining_tpu.models import convert
        logger.info("loading pretrained weights from %s", self.cfg.init_from)
        return convert.load_params(self.cfg.init_from, self.model_cfg)

    _test_docs_cache = None

    def _test_docs(self) -> list[str]:
        if self._test_docs_cache is None:
            self._test_docs_cache = text_corpus(
                split="test", source=self.cfg.dataset,
                n_docs=max(256, self.cfg.n_docs // 8))
        return self._test_docs_cache

    def _batches_over(self, docs) -> Callable[[], Iterable[dict]]:
        cfg = self.cfg

        def factory():
            it = batch_iterator(docs, self.tokenizer,
                                batch_size=cfg.batch_size,
                                seq_len=cfg.eval_seq_len,
                                max_vocab=self.model_cfg.vocab_size)
            for i, b in enumerate(it):
                if i >= cfg.eval_batches:
                    break
                yield b

        return factory

    def eval_batches(self) -> Callable[[], Iterable[dict]]:
        """SERVER-side held-out shard (validator scoring, averager
        meta-learning/publish guard): the FRONT half of the test split —
        the reference evaluates the first ~100 test texts
        (neurons/validator.py:49,98). The back half is reserved for miner
        self-validation (``miner_val_batches``), keeping the two roles'
        eval data disjoint."""
        docs = self._test_docs()
        return self._batches_over(docs[: max(1, len(docs) // 2)]
                                  if len(docs) >= 4 else docs)

    def miner_val_batches(self) -> Callable[[], Iterable[dict]]:
        """Miner self-validation shard: a per-hotkey-offset rotation of the
        BACK half of the test split, disjoint from the validator's shard
        (round-5 advisor: a miner guarding on the IDENTICAL shard the
        validator scores biases its published state toward that shard by
        selection — its score reads high by construction). The per-hotkey
        rotation additionally decorrelates which windows different miners
        overfit toward, like shuffle_seed_for does for train order."""
        docs = self._test_docs()
        if len(docs) < 4:
            logger.warning(
                "test split too small (%d docs) to give the miner a "
                "disjoint self-eval shard; guard evals will share the "
                "validator's data", len(docs))
            tail = docs
        else:
            tail = docs[len(docs) // 2:]
        off = shuffle_seed_for(self.cfg.hotkey) % len(tail)
        return self._batches_over(tail[off:] + tail[:off])


@dataclasses.dataclass
class HealthPlane:
    """The role's slice of the fleet health plane (engine/health.py):
    its own heartbeat publisher, optionally a FleetMonitor (validator/
    averager), optionally the remediation engine acting on that
    monitor's breaches (engine/remediate.py, ``--remediate``), and
    optionally the Prometheus exporter (--obs-port)."""
    heartbeat: Any = None
    fleet: Any = None
    remediation: Any = None
    exporter: Any = None

    def close(self) -> None:
        """Idempotent teardown in dependency order (exporter may render
        the fleet ledger until the moment it stops serving)."""
        if self.exporter is not None:
            self.exporter.close()
        if self.heartbeat is not None:
            self.heartbeat.close()
        if self.fleet is not None:
            self.fleet.close()


def build_health_plane(cfg: RunConfig, c: Components, *,
                       vitals=None, monitor: bool = False,
                       anomaly=None,
                       collect=None,
                       start_heartbeat: bool = True) -> HealthPlane:
    """Assemble the role's health plane from config: a heartbeat
    publisher when ``--heartbeat-interval`` > 0 (``vitals`` supplies the
    body — engine/health.report_vitals over the role's report), a
    FleetMonitor for the delta-consuming roles (``monitor=True``), and
    the ``--obs-port`` exporter. Pod rule: only the coordinator
    publishes heartbeats or monitors the fleet (writes are gated there
    anyway, and N identical monitors would multiply probe traffic);
    the exporter serves per host — per-process registries differ."""
    from distributedtraining_tpu.parallel import multihost

    plane = HealthPlane()
    coordinator = multihost.is_coordinator()
    if cfg.heartbeat_interval > 0 and coordinator:
        from distributedtraining_tpu.engine.health import (FleetMonitor,
                                                           HeartbeatPublisher)
        if monitor:
            plane.fleet = FleetMonitor(c.transport, metrics=c.metrics,
                                       anomaly=anomaly)
            if cfg.remediate:
                from distributedtraining_tpu.engine.remediate import (
                    RemediationEngine, RemediationPolicy)
                rules = tuple(r.strip()
                              for r in cfg.quarantine_rules.split(",")
                              if r.strip())
                plane.remediation = RemediationEngine(
                    plane.fleet, metrics=c.metrics,
                    policy=RemediationPolicy(
                        quarantine_rules=rules,
                        probation_beats=cfg.probation_beats,
                        probation_rounds=cfg.probation_rounds,
                        score_decay=cfg.score_decay))
        plane.heartbeat = HeartbeatPublisher(
            c.transport, cfg.role, cfg.hotkey,
            interval=cfg.heartbeat_interval, vitals=vitals,
            collect=collect)
        if start_heartbeat:
            plane.heartbeat.start()
    elif cfg.remediate and coordinator:
        logger.warning(
            "--remediate has no effect without --heartbeat-interval > 0: "
            "remediation acts on SLO breaches, and breaches come from the "
            "heartbeat-fed FleetMonitor")
    if cfg.obs_port:
        from distributedtraining_tpu.utils.obs_http import ObsHTTPExporter
        plane.exporter = ObsHTTPExporter(
            cfg.obs_port, fleet=plane.fleet, role=cfg.role,
            profile_dir=os.path.join(cfg.work_dir, "debug_traces",
                                     cfg.hotkey))
        plane.exporter.start()
    return plane


def build_base_fetcher(cfg: RunConfig, c: Components):
    """The role's content-addressed base fetcher
    (engine/basedist.BaseFetcher) when ``--base-wire-v2`` is on, else
    None (the monolithic reference pull). Mirrors come from
    ``--base-mirrors`` (the averager's announce rider extends the list
    at fetch time). Single-host machinery — pods keep the coordinator
    broadcast path, so they get None."""
    import jax

    if not cfg.base_wire_v2 or jax.process_count() > 1:
        return None
    from distributedtraining_tpu.engine.basedist import BaseFetcher
    mirrors = [m.strip() for m in (cfg.base_mirrors or "").split(",")
               if m.strip()]
    return BaseFetcher(c.transport, mirrors=mirrors,
                       store_bytes=cfg.base_store_mb * (1 << 20))


def build(cfg: RunConfig) -> Components:
    import jax

    from distributedtraining_tpu.parallel import multihost

    # config 5 (multi-host pod): env-gated no-op on a single host; on a pod
    # every process of the role runs this same build and forms one SPMD
    # program over the global mesh
    multihost.initialize(coordinator_address=cfg.multihost_coordinator,
                         num_processes=cfg.multihost_processes,
                         process_id=cfg.multihost_id)

    # before ANY jit dispatch so the whole build benefits
    enable_compile_cache()

    import dataclasses as _dc

    family = family_of(cfg.model)
    model_cfg = family.PRESETS[cfg.model]
    if cfg.scan_blocks:
        model_cfg = _dc.replace(model_cfg, scan_blocks=True)
    if cfg.logits_dtype:
        model_cfg = _dc.replace(model_cfg, logits_dtype=cfg.logits_dtype)
    if cfg.remat is not None:   # tri-state: None = keep the preset's default
        model_cfg = _dc.replace(model_cfg, remat=cfg.remat)
    model, model_cfg = family.make_model(model_cfg)

    mesh = None
    spec = cfg.mesh
    n_params = 0
    if spec.auto:
        import numpy as _np
        abstract = jax.eval_shape(
            lambda: model.init_params(jax.random.PRNGKey(0)))
        n_params = sum(int(_np.prod(l.shape))
                       for l in jax.tree_util.tree_leaves(abstract))
    if jax.process_count() > 1:
        rcfg = resolve_mesh_config(
            n_devices=len(jax.devices()), dp=spec.dp, fsdp=spec.fsdp,
            sp=spec.sp, tp=spec.tp, auto=spec.auto, model_params=n_params,
            dcn_dp=spec.dcn_dp)
        mesh = multihost.pod_mesh(dp=rcfg.dp, fsdp=rcfg.fsdp, sp=rcfg.sp,
                                  tp=rcfg.tp, dcn_dp=spec.dcn_dp)
    else:
        mcfg = resolve_mesh_config(
            n_devices=len(jax.devices()), dp=spec.dp, fsdp=spec.fsdp,
            sp=spec.sp, tp=spec.tp, auto=spec.auto, model_params=n_params,
            dcn_dp=spec.dcn_dp)
        if mcfg.n_devices > 1:
            mesh = make_mesh(mcfg)

    seq = cfg.seq_len if cfg.role == "miner" else cfg.eval_seq_len
    engine = TrainEngine(
        model,
        optimizer=default_optimizer(cfg.learning_rate,
                                    grad_clip=cfg.grad_clip,
                                    weight_decay=cfg.weight_decay,
                                    mu_dtype=cfg.mu_dtype,
                                    is_buffer=model_cfg.is_buffer),
        mesh=mesh, seq_len=seq, fused_loss=cfg.fused_loss,
        accum_steps=cfg.accum_steps)

    if cfg.backend == "memory":
        transport = InMemoryTransport()
    elif cfg.backend == "hf":
        if not cfg.averaged_model_repo_id:
            raise SystemExit(
                "--backend hf requires --averaged-model-repo-id")
        if cfg.role == "miner" and not cfg.my_repo_id:
            raise SystemExit("--backend hf miner requires --my-repo-id")
        from distributedtraining_tpu.transport import HFHubTransport
        transport = HFHubTransport(
            averaged_model_repo_id=cfg.averaged_model_repo_id,
            my_repo_id=cfg.my_repo_id,
            owns_base_repo=(cfg.role == "averager"))
    else:
        transport = LocalFSTransport(os.path.join(cfg.work_dir, "artifacts"))

    if cfg.chain == "bittensor":
        from distributedtraining_tpu.chain import (BittensorAddressStore,
                                                   BittensorChain)
        chain = BittensorChain(netuid=cfg.netuid,
                               wallet_name=cfg.wallet_name,
                               wallet_hotkey=cfg.wallet_hotkey,
                               network=cfg.subtensor_network,
                               epoch_length=cfg.epoch_length,
                               resync_blocks=cfg.resync_blocks,
                               vpermit_stake_limit=cfg.vpermit_stake_limit)
        # chain._rpc carries the deadline + per-call connection capture +
        # lazy-recycle discipline; injecting it keeps store and chain on
        # ONE live connection instead of desynchronizing after a recycle
        address_store = BittensorAddressStore(
            chain.subtensor, cfg.netuid, wallet=chain.wallet,
            rpc=chain._rpc)
    else:
        if cfg.backend == "hf":
            # deltas would flow through the Hub while scores stay in a
            # machine-local JSON no other participant can read
            logger.warning(
                "--backend hf with --chain local: chain state (scores, "
                "weights, repo registry) is local to this machine; use "
                "--chain bittensor for a multi-host deployment")
        chain_dir = os.path.join(cfg.work_dir, "chain")
        chain = LocalChain(chain_dir, my_hotkey=cfg.hotkey,
                           epoch_length=cfg.epoch_length,
                           vpermit_stake_limit=cfg.vpermit_stake_limit)
        address_store = LocalAddressStore(chain_dir)
    # artifact authenticity: sign publishes, verify fetches against
    # registered pubkeys (reference anchor: repo ownership + hotkey-signed
    # metrics, dummy_miner.py:63-68). Wrapped INSIDE the coordinator gate so
    # pod writes stay coordinator-only.
    identity = None
    if cfg.sign_artifacts:
        from distributedtraining_tpu.transport import SignedTransport
        from distributedtraining_tpu.utils.identity import Identity
        wallet_path = cfg.wallet_path or os.path.join(
            cfg.work_dir, "wallets", f"{cfg.hotkey}.json")
        # pod roles: ONLY the coordinator holds a signing identity — its
        # publishes are the only ones that leave the pod (gate_io), and N
        # processes generate-and-saving to one shared wallet path would
        # race, registering one process's key while another's lands in the
        # file (bricking the hotkey under first-write-wins on next boot)
        if multihost.is_coordinator():
            if os.path.exists(wallet_path):
                identity = Identity.load(wallet_path)
            else:
                identity = Identity.generate()
                identity.save(wallet_path)
                logger.info("generated signing identity %s at %s",
                            identity.hotkey, wallet_path)
        base_signer = cfg.base_signer or (
            cfg.hotkey if cfg.role == "averager" else None)
        transport = SignedTransport(
            transport, identity=identity,
            pubkey_resolver=address_store.retrieve_pubkey,
            base_signer=base_signer, my_hotkey=cfg.hotkey)
        register_ok = True
        if multihost.is_coordinator():
            try:
                address_store.store_pubkey(cfg.hotkey, identity.public_bytes)
            except ValueError:
                register_ok = False
        if jax.process_count() > 1:
            # every process must learn the coordinator's verdict: a
            # coordinator-only SystemExit would leave the workers alive and
            # hung at their first collective
            import numpy as _np
            from jax.experimental import multihost_utils as _mhu
            register_ok = bool(_mhu.broadcast_one_to_all(
                _np.asarray(register_ok, _np.int32)))
        if not register_ok:
            # key already registered for this hotkey and differs — a
            # rotated local wallet must fail loudly, not publish
            # artifacts every peer will reject
            raise SystemExit(
                f"hotkey {cfg.hotkey} has a different registered "
                f"pubkey; restore the original wallet file or use a "
                f"new hotkey")
    if cfg.chaos_spec:
        # deterministic fault injection (transport/chaos.py): wraps the
        # OUTERMOST transport layer so injected faults hit signed
        # publishes and verified fetches exactly like network faults
        # would. Soak/test machinery — the flag warns on every boot.
        from distributedtraining_tpu.transport.chaos import (ChaosSpec,
                                                             ChaosTransport)
        logger.warning("CHAOS INJECTION ACTIVE for role %s: %s",
                       cfg.role, cfg.chaos_spec)
        transport = ChaosTransport(transport,
                                   ChaosSpec.from_json(cfg.chaos_spec),
                                   role=cfg.role)
    # only the coordinator process of a pod role may write to the outside
    # world (delta pushes, base publishes, weight sets)
    transport, chain = multihost.gate_io(transport, chain)
    if jax.process_count() > 1 and cfg.backend != "hf":
        # reads pass through the gate on every process: with per-host
        # storage, workers would never observe published bases and diverge
        logger.warning(
            "multi-host run with --backend %s: every host reads %s "
            "directly — it MUST be shared storage (NFS/gcsfuse) across all "
            "hosts, or use --backend hf", cfg.backend, cfg.work_dir)

    if cfg.my_repo_id and multihost.is_coordinator():
        # advertise our repo like the reference miner does on-chain
        # (neurons/miner.py:36-44)
        address_store.store_repo(cfg.hotkey, cfg.my_repo_id)

    if cfg.tokenizer == "byte" or (cfg.tokenizer == "auto"
                                   and model_cfg.vocab_size < 50257):
        tokenizer = ByteTokenizer()
    elif cfg.tokenizer == "word":
        # corpus-fit word vocab, deterministic per corpus: every role of a
        # deployment rebuilds the identical mapping with no shared artifact
        # (the offline stand-in for the GPT-2 BPE — scripts/e2e_round.py)
        from distributedtraining_tpu.data import WordTokenizer
        tokenizer = WordTokenizer(
            text_corpus(split="train", source=cfg.dataset),
            vocab_size=model_cfg.vocab_size)
    elif cfg.tokenizer == "bpe":
        # REAL byte-level BPE (GPT-2's algorithm) trained locally on the
        # machine's own text — the big-vocab production tokenizer with
        # zero egress (data/bpe.py). Saved under the work_dir so the
        # three roles of a deployment train it once.
        from distributedtraining_tpu.data.bpe import BPETokenizer
        tokenizer = BPETokenizer.train_or_load(
            os.path.join(cfg.work_dir, "tokenizer",
                         f"bpe-{min(model_cfg.vocab_size, 32000)}.json"),
            vocab_size=min(model_cfg.vocab_size, 32000))
    else:
        tokenizer = load_tokenizer(
            "gpt2" if cfg.tokenizer == "auto" else cfg.tokenizer)

    sinks = []
    if cfg.metrics_path:
        sinks.append(JSONLSink(
            cfg.metrics_path,
            max_bytes=(cfg.metrics_rotate_mb * (1 << 20)
                       if cfg.metrics_rotate_mb > 0 else None),
            keep_segments=max(1, cfg.metrics_keep_segments)))
    if cfg.mlflow_uri:
        from distributedtraining_tpu.utils.metrics import MLflowSink
        sinks.append(MLflowSink(tracking_uri=cfg.mlflow_uri,
                                experiment=f"hivetrain-{cfg.netuid}",
                                run_name=f"{cfg.role}-{cfg.hotkey}"))
    metrics = multi_sink(*sinks) if sinks else None
    if metrics is not None:
        # bind the process-wide span/counter emitter (utils/obs.py) to
        # this role's sink: every engine/transport span and registry
        # flush lands in the same JSONL the scalar metrics do, which is
        # what scripts/obs_report.py joins across roles. Role mains reset
        # it on exit so sequential in-process role runs (e2e) stay clean.
        from distributedtraining_tpu.utils import obs
        obs.configure(metrics, role=cfg.role)
        if cfg.devprof:
            # device observatory (utils/devprof.py): per-program cost
            # attribution + roofline gauges on every registered hot
            # path; rides the same sink via the obs.flush hook. Role
            # mains reset it alongside obs on exit.
            from distributedtraining_tpu.utils import devprof
            devprof.enable()
    if cfg.flight_events > 0:
        # flight recorder (utils/flight.py): the bounded forensic ring
        # every role keeps, frozen into a transport-published __pm__
        # bundle on SLO breach / remediation / crash. Configured on every
        # process — bundle PUBLISHES ride the coordinator-gated transport
        # like any other write, so pod workers record locally and ship
        # nothing. Role mains install the crash hooks and call
        # flight.shutdown() on exit.
        from distributedtraining_tpu.utils import flight
        flight.configure(cfg.role, cfg.hotkey, transport=transport,
                         capacity=cfg.flight_events, config=cfg)

    lora_cfg = None
    if cfg.lora_rank > 0:
        from distributedtraining_tpu.models.lora import LoRAConfig
        lora_cfg = LoRAConfig(rank=cfg.lora_rank, alpha=cfg.lora_alpha)

    return Components(cfg=cfg, model=model, model_cfg=model_cfg,
                      engine=engine, transport=transport, chain=chain,
                      address_store=address_store, tokenizer=tokenizer,
                      metrics=metrics, lora_cfg=lora_cfg)
