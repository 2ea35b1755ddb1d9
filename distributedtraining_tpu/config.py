"""Run configuration: dataclasses + a CLI builder, parsed only from main().

The reference merges bittensor arg groups with subnet args into one bt.config
namespace (hivetrain/config/config.py:44-60) and — worse — parses sys.argv at
module import time (training_manager.py:22-24), which SURVEY.md §1 flags as
the defect that makes the library unimportable without a chain. Here the
config is a plain dataclass; ``RunConfig.from_args`` is called explicitly by
the role entry points (neurons/) and never at import.

Flag parity map (reference → here):
  --netuid                      → --netuid           (base_subnet_config.py)
  --wallet.hotkey               → --hotkey
  --storage.my_repo_id          → --my-repo-id       (hivetrain_config.py:14)
  --storage.averaged_model_repo_id → --averaged-model-repo-id (:15)
  --storage.gradient_dir/model_dir → --work-dir      (:16-17)
  --batch_size                  → --batch-size       (:34-41)
  --neuron.epoch_length         → --epoch-length     (base_subnet_config.py:72)
  --neuron.vpermit_tao_limit    → --vpermit-stake-limit (:178-183)
  --mock                        → --backend local    (:79-84)
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Optional, Sequence


@dataclasses.dataclass
class MeshSpec:
    """dp×fsdp×sp×tp axis sizes; 0 for dp means "all visible devices".
    dcn_dp > 1 lays the outermost dp groups across the slow network
    (multi-slice DCN) — see parallel.multihost.pod_mesh."""
    dp: int = 0
    fsdp: int = 1
    sp: int = 1
    tp: int = 1
    dcn_dp: int = 1
    auto: bool = False   # pick axes from model size (best_mesh_shape)


@dataclasses.dataclass
class RunConfig:
    role: str = "miner"                      # miner | validator | averager

    # -- identity / chain ---------------------------------------------------
    chain: str = "local"                     # local | bittensor
    netuid: int = 25                         # prod subnet (README.md:93)
    hotkey: str = "hotkey_0"
    wallet_name: str = "default"             # bittensor wallet (cold) name
    wallet_hotkey: str = "default"           # bittensor wallet hotkey name
    subtensor_network: str = "finney"        # bittensor network endpoint
    epoch_length: int = 100                  # blocks between weight sets
    vpermit_stake_limit: float = 1000.0
    allow_no_vpermit: bool = False           # run an unpermitted validator
    resync_blocks: int = 0                   # metagraph resync throttle

    # -- storage / transport ------------------------------------------------
    backend: str = "local"                   # local | memory | hf
    work_dir: str = "./hivetrain_run"
    my_repo_id: Optional[str] = None
    averaged_model_repo_id: Optional[str] = None

    # -- artifact authenticity (transport/signed.py) ------------------------
    sign_artifacts: bool = False             # Ed25519-envelope publishes
    wallet_path: Optional[str] = None        # default: <work_dir>/wallets/<hotkey>.json
    base_signer: Optional[str] = None        # hotkey expected to sign the base

    # -- model / optimization ----------------------------------------------
    model: str = "gpt2-124m"                 # gpt2/llama preset name
    init_from: Optional[str] = None          # pretrained weights (hf:<repo>,
                                             # dir, or .safetensors/.bin path)
    seq_len: int = 64                        # miner train len (miner.py:70)
    eval_seq_len: int = 512                  # validator len (validator.py:63)
    batch_size: int = 8
    eval_batches: int = 12                   # ~100 texts / batch 8 (ref :49,98)
    score_metric: str = "loss"               # loss | perplexity (ref :93-97)
    max_delta_abs: float = 1e3               # admission magnitude cap (0=off)
    accept_quant: bool = True                # accept int8-wire submissions
    stale_deltas: Optional[str] = None       # skip|accept (None = role default)
    learning_rate: float = 5e-4              # neurons/miner.py:121-128
    weight_decay: float = 0.01               # AdamW decoupled decay
    grad_clip: Optional[float] = None
    mu_dtype: Optional[str] = None           # "bfloat16": half-size Adam mu
    lora_rank: int = 0                       # >0: LoRA-delta mode (config 4)
    lora_alpha: float = 16.0
    dataset: str = "auto"                    # auto | wikitext | synthetic
    n_docs: int = 256                        # corpus cap fed to text_corpus
    tokenizer: str = "auto"                  # auto | byte | <hf name>
    fused_loss: bool = False                 # tiled-head CE (no [B,T,V] logits)
    scan_blocks: bool = False                # lax.scan the block stack
    logits_dtype: Optional[str] = None       # "bfloat16": half-size logits buf
    delta_dtype: Optional[str] = None        # bf16/int8/sparse8 wire deltas
    delta_density: float = 1.0 / 64.0        # sparse8 kept-coordinate ratio
    # wire v2 (ROADMAP item 1): sparse+quantized packed deltas published
    # as content-addressed per-layer shards + manifest (delta.pack_delta_v2,
    # serialization shard container, engine/publish.py uploads only
    # changed shards, engine/ingest.py fetches only changed shards)
    wire_v2: bool = False                    # miner: publish the v2 wire
    wire_density: float = 1.0 / 64.0         # v2 kept-coordinate ratio
    wire_quant: str = "int8"                 # v2 kept values: int8 | none
    accept_wire_v2: bool = True              # receivers: decode v2 manifests
    # content-addressed base distribution (engine/basedist.py): the
    # averager publishes hash-addressed per-layer base shards + a signed
    # per-revision manifest next to the monolithic base; fetchers
    # delta-pull only changed-hash layers, racing __mirror__ replicas
    # before the origin. The monolithic artifact stays the fallback, so
    # mixed old/new fleets interoperate with no flag day.
    base_wire_v2: bool = True                # sharded publish + delta-pull
    base_mirrors: str = ""                   # comma list of mirror nodes
    base_mirror: bool = True                 # sub-averagers: mirror duty
    base_store_mb: int = 1024                # local shard-store budget
    remat: Optional[bool] = None             # per-block rematerialization
    prefetch_depth: int = 2                  # host pipeline look-ahead (0=off)
    accum_steps: int = 1                     # microbatches per optimizer step

    # -- serving plane (engine/serve.py; neurons/server.py) -----------------
    serve_port: int = 0                      # HTTP /generate port (0 = off)
    serve_slots: int = 8                     # concurrent decode slots
    serve_page_size: int = 16                # KV-cache page, in tokens
    serve_kv_pages: int = 0                  # page-pool size (0 = auto)
    serve_max_new: int = 64                  # default max_new_tokens
    serve_max_seq: int = 0                   # cache len cap (0 = model max)
    serve_max_queue: int = 0                 # shed past this depth (0 = off)
    serve_prefix_cache: bool = True          # shared-prefix KV page reuse
    serve_speculative: bool = False          # draft-verify speculative decode
    serve_draft_k: int = 4                   # drafted tokens per slot/step
    serve_draft_repo: str = ""               # draft base: "preset@work_dir"
    serve_trace: bool = True                 # request-scoped stage traces
    serve_trace_exemplars: int = 4           # K slowest frozen per window
    serve_trace_window: float = 30.0         # exemplar window (seconds)
    serve_phase: str = "unified"             # unified | prefill | decode
    swap_policy: str = "drain"               # drain | restart
    swap_poll: float = 15.0                  # base-revision poll (seconds)

    # -- mesh ---------------------------------------------------------------
    mesh: MeshSpec = dataclasses.field(default_factory=MeshSpec)

    # -- multi-host (config 5); None = auto-detect from the environment -----
    multihost_coordinator: Optional[str] = None   # host:port of process 0
    multihost_processes: Optional[int] = None
    multihost_id: Optional[int] = None

    # -- cadences (seconds) -------------------------------------------------
    send_interval: float = 800.0             # miner.py:125
    # async publication pipeline (engine/publish.py): overlap the miner's
    # delta push / rider / checkpoint I/O with training compute; a push
    # still in flight at the next interval is superseded, never queued.
    # --no-push-async restores the fully sequential reference path.
    push_async: bool = True
    push_queue_depth: int = 1                # pending pushes before supersede
    check_update_interval: float = 300.0
    # miner self-validation guard: the miner scores its own candidate on
    # the held-out shard every ``self_eval_interval`` seconds and reverts
    # to its best-seen state after ``self_eval_patience`` non-improving
    # evals (engine/train.py MinerLoop._val_guard). -1 = follow
    # send_interval (default on); 0 disables (reference-parity blind
    # training, training_manager.py:380-392)
    self_eval_interval: float = -1.0
    self_eval_patience: int = 3
    self_eval_margin: float = 0.1
    keep_optimizer_on_pull: bool = False     # ref parity: reset on pull
    checkpoint_interval: float = 600.0       # 0 disables local checkpointing
    checkpoint_dir: Optional[str] = None     # default: <work_dir>/checkpoints/<hotkey>
    validation_interval: float = 1800.0      # validator.py:112
    val_cohort: int = 8                      # miners scored per batched eval
    #                                          pass (<=1 = sequential legacy)
    val_pipeline_depth: int = 1              # cohorts staged ahead of eval
    #                                          (0 disables fetch/eval overlap)
    averaging_interval: float = 1200.0       # averager.py:106
    # concurrent revision-aware ingest (engine/ingest.py, validator +
    # averager): fetch-pool width (1 = serial fetch order) and the
    # content-addressed host cache's byte budget (0 disables — every
    # round re-downloads every artifact, the reference's behavior)
    ingest_workers: int = 4
    ingest_cache_mb: int = 2048

    # -- averager strategy --------------------------------------------------
    strategy: str = "parameterized"          # weighted | parameterized | genetic
    publish_policy: str = "improved"         # improved | always (ref parity)
    merge_chunk: int = 8                     # weighted-merge device chunk
    meta_epochs: int = 7                     # averager.py:106
    genetic_population: int = 10             # averaging_logic.py:830-970
    genetic_generations: int = 10
    genetic_sigma: float = 0.1
    genetic_screen_batches: int = 2          # 0 = full-set fitness
    meta_lr: float = 0.01
    meta_optimizer: str = "adam"             # adam | sgd (ref spelling)
    outer_momentum: float = 0.0              # >0 wraps strategy in OuterOptMerge
    outer_lr: float = 0.7                    # DiLoCo-style outer Nesterov step

    # -- hierarchical aggregation (engine/hier_average.py) ------------------
    # --hier sub: this averager is a SUB-AVERAGER — it gathers its
    # plan_fanout slice of the metagraph and publishes the partial
    # aggregate under the reserved __agg__.<node> id instead of merging
    # the whole fleet. --hier root: gather the configured sub nodes'
    # aggregates (never the metagraph) and publish the base. "" = the
    # flat single-averager reference topology.
    hier: str = ""                           # "" | sub | root
    hier_node: str = ""                      # sub node id (default: hotkey)
    hier_nodes: str = ""                     # comma list of sub node ids
    hier_fanout: int = 0                     # auto-plan width when no list
    hier_wire_v2: bool = False               # aggregates ride the v2 wire

    # -- remediation / failover (engine/remediate.py) -----------------------
    # --remediate closes the loop from SLO breach to action on the
    # monitor roles: quarantine + probation for breaching miners, score
    # decay, and elastic cohort sizing over the compiled-bucket ladder.
    # Requires the health plane (--heartbeat-interval > 0) for breaches
    # to exist at all.
    remediate: bool = False
    quarantine_rules: str = "push_failure_streak,loss_divergence,stale_node"
    probation_beats: int = 3                 # clean beats to re-admit
    probation_rounds: int = 2                # rounds on probation after
    score_decay: float = 0.25                # per-round quarantined decay
    # averager failover: --standby starts a PASSIVE averager that follows
    # the primary's lease/heartbeat/base-revision and takes over
    # publication (lease epoch + 1) after --failover-deadline seconds of
    # silence (0 = 3x --averaging-interval). The primary holds the lease
    # whenever --remediate or --standby fleets are in play.
    standby: bool = False
    failover_deadline: float = 0.0

    # -- chaos injection (transport/chaos.py; soaks and tests only) ----------
    # JSON ChaosSpec wrapping this role's transport, e.g.
    # '{"fetch_error_rate": 0.1, "latency_s": 0.05, "seed": 7}' — faults
    # are deterministic per (seed, op sequence). Never set in production.
    chaos_spec: Optional[str] = None

    # -- bounded runs (tests / smoke) --------------------------------------
    max_steps: Optional[int] = None
    rounds: Optional[int] = None

    # -- observability ------------------------------------------------------
    metrics_path: Optional[str] = None       # JSONL sink
    # size-based JSONL rotation: rotate the --metrics-path file once it
    # passes this many MB, keeping the newest --metrics-keep-segments
    # rotated segments (0 = never rotate, the historical single-file
    # behavior; obs_report/fleet_report read rotated runs transparently)
    metrics_rotate_mb: int = 0
    metrics_keep_segments: int = 3
    log_every: int = 1000                    # train steps between metric logs
                                             # (ref :394-402)
    # fleet health plane (engine/health.py): >0 publishes a versioned
    # heartbeat through the transport every N seconds; the validator and
    # averager additionally run the FleetMonitor (contribution ledger +
    # SLO rules) over the fleet's heartbeats. 0 disables the plane.
    heartbeat_interval: float = 0.0
    # zero-dependency Prometheus-text exporter (utils/obs_http.py):
    # serve the obs registry (+ fleet ledger, where one exists) on
    # http://127.0.0.1:<port>/metrics — plus the postmortem debug
    # endpoints (/debug/dump, /debug/profile, /debug/stacks). 0 disables.
    obs_port: int = 0
    # flight recorder (utils/flight.py): bounded in-memory ring of
    # structured events (spans, SLO fires, lease flips, publish/swap
    # outcomes, heartbeats, sanitized config) frozen into a
    # content-addressed __pm__ postmortem bundle on SLO breach /
    # remediation action / crash, published through this role's
    # transport. Value = ring capacity in events; 0 disables the plane.
    flight_events: int = 512
    # device performance observatory (utils/devprof.py): per-program XLA
    # cost attribution (FLOPs/bytes), compile + execution histograms,
    # and roofline achieved-fraction gauges for every registered hot
    # path; exposed via obs_http dt_prog_* series, heartbeat anat.*
    # fields, and the {"devprof": ...} JSONL record perf_report joins.
    # On by default wherever a metrics sink is configured (its cost on
    # the chip: not measured; tests/test_planes.py holds that it sees
    # every train-step dispatch and changes no result).
    devprof: bool = True
    # lineage/provenance plane (engine/lineage.py): the averager (and
    # every sub-averager) freezes a content-addressed __lineage__ record
    # per landed merge — parent revision, the exact contribution set and
    # weights — and runs the EWMA/CUSUM quality-drift detector over the
    # merged held-out loss. Records are KBs beside a full-model base
    # publish (one record per merged round, the published base
    # unchanged: tests/test_planes.py).
    lineage: bool = True
    mlflow_uri: Optional[str] = None
    profile_dir: Optional[str] = None        # jax.profiler trace capture
    profile_steps: int = 5                   # train steps per capture
    # anomaly-triggered capture (utils/obs.AnomalyMonitor): a loss spike,
    # push-failure streak, or step-time p99 blowout arms ONE disarmed
    # TraceCapture automatically — profiler evidence of the first anomaly
    # lands on disk without anyone watching
    anomaly_trace: bool = True
    anomaly_dir: Optional[str] = None        # default: <work_dir>/anomaly_traces/<hotkey>

    @classmethod
    def from_args(cls, role: str, argv: Sequence[str] | None = None
                  ) -> "RunConfig":
        ns = build_parser(role).parse_args(argv)
        mesh = MeshSpec(dp=ns.dp, fsdp=ns.fsdp, sp=ns.sp, tp=ns.tp,
                        dcn_dp=ns.dcn_dp, auto=ns.mesh_auto)
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in vars(ns).items() if k in fields}
        kw.pop("mesh", None)
        return cls(role=role, mesh=mesh, **kw)


def _nonneg_float(value: str) -> float:
    f = float(value)
    if f < 0:
        raise argparse.ArgumentTypeError(
            f"{value}: must be >= 0 (0 disables)")
    return f


def _dataset_arg(value: str) -> str:
    if value in ("auto", "wikitext", "synthetic") or \
            value.startswith("files:"):
        return value
    raise argparse.ArgumentTypeError(
        f"{value!r}: expected auto, wikitext, synthetic, or files:<glob>")


def build_parser(role: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog=f"neurons/{role}.py",
                                description=f"hivetrain-tpu {role}")
    d = RunConfig()

    g = p.add_argument_group("chain")
    g.add_argument("--chain", choices=("local", "bittensor"), default=d.chain,
                   help="local = JSON-file chain under --work-dir (single "
                        "box / tests); bittensor = substrate chain via the "
                        "bittensor SDK. A multi-host --backend hf deployment "
                        "needs --chain bittensor or every role sees only its "
                        "own local scores.")
    g.add_argument("--netuid", type=int, default=d.netuid)
    g.add_argument("--hotkey", default=d.hotkey)
    g.add_argument("--wallet-name", dest="wallet_name", default=d.wallet_name)
    g.add_argument("--wallet-hotkey", dest="wallet_hotkey",
                   default=d.wallet_hotkey)
    g.add_argument("--subtensor-network", dest="subtensor_network",
                   default=d.subtensor_network)
    g.add_argument("--epoch-length", dest="epoch_length", type=int,
                   default=d.epoch_length)
    g.add_argument("--resync-blocks", dest="resync_blocks", type=int,
                   default=d.resync_blocks,
                   help="serve the cached metagraph within this many blocks "
                        "of the last resync (0 = resync every sync call); "
                        "bittensor chain only")
    g.add_argument("--vpermit-stake-limit", dest="vpermit_stake_limit",
                   type=float, default=d.vpermit_stake_limit)
    if role == "validator":
        g.add_argument("--allow-no-vpermit", dest="allow_no_vpermit",
                       action="store_true",
                       help="run even when this hotkey holds no validator "
                            "stake (scores are computed but weights are "
                            "never emitted; useful for dry runs)")
        g.add_argument("--score-metric", dest="score_metric",
                       choices=("loss", "perplexity"),
                       default=d.score_metric,
                       help="scoring rule: max(0, base - candidate) on "
                            "eval loss or on perplexity (the reference's "
                            "two modes, validation_logic.py:93-97)")

    g = p.add_argument_group("storage")
    g.add_argument("--backend", choices=("local", "memory", "hf"),
                   default=d.backend)
    g.add_argument("--work-dir", dest="work_dir", default=d.work_dir)
    g.add_argument("--my-repo-id", dest="my_repo_id", default=None)
    g.add_argument("--averaged-model-repo-id", dest="averaged_model_repo_id",
                   default=None)
    g.add_argument("--sign-artifacts", dest="sign_artifacts",
                   action="store_true",
                   help="publish artifacts in Ed25519 signature envelopes "
                        "and verify peers' signatures against their "
                        "registered pubkeys (transport/signed.py)")
    g.add_argument("--wallet-path", dest="wallet_path", default=None,
                   help="identity keyfile for --sign-artifacts (created if "
                        "missing); default <work-dir>/wallets/<hotkey>.json")
    g.add_argument("--base-signer", dest="base_signer", default=None,
                   help="hotkey expected to sign the published base model "
                        "(the averager's); with a registered pubkey, base "
                        "fetches then REQUIRE a valid signature")
    g.add_argument("--base-wire-v2", dest="base_wire_v2",
                   action="store_true", default=d.base_wire_v2,
                   help="content-addressed sharded base distribution "
                        "(engine/basedist.py; default ON): the averager "
                        "publishes each base as hash-addressed per-layer "
                        "shards + a signed per-revision manifest NEXT TO "
                        "the monolithic artifact, and fetchers pull only "
                        "changed-hash layers (unchanged layer = 0 bytes), "
                        "racing any mirror that has the hash before the "
                        "origin. Mixed fleets need no flag day: the "
                        "monolithic base stays the fallback")
    g.add_argument("--no-base-wire-v2", dest="base_wire_v2",
                   action="store_false",
                   help="monolithic-only base distribution (the reference "
                        "posture): the averager publishes no shard plane "
                        "and fetchers never probe for manifests")
    g.add_argument("--base-mirrors", dest="base_mirrors",
                   default=d.base_mirrors,
                   help="comma list of mirror node ids this fetcher races "
                        "for base shards before the origin (normally the "
                        "fleet's __agg__ sub-averager nodes; the "
                        "averager's announce rider extends the list at "
                        "run time)")
    g.add_argument("--no-base-mirror", dest="base_mirror",
                   action="store_false", default=d.base_mirror,
                   help="sub-averagers only: do NOT re-publish base "
                        "shards under this node's __mirror__ slots")
    g.add_argument("--base-store-mb", dest="base_store_mb", type=int,
                   default=d.base_store_mb,
                   help="byte budget of the local content-addressed base "
                        "shard store (the delta-pull dedupe memory; 0 "
                        "disables caching — every sharded pull re-fetches "
                        "all layers)")

    g = p.add_argument_group("model")
    g.add_argument("--model", default=d.model)
    g.add_argument("--init-from", dest="init_from", default=None,
                   help="pretrained checkpoint to start from when no base "
                        "is published yet: hf:<repo_id> (local HF cache), a "
                        "checkpoint directory, or a .safetensors/.bin file "
                        "(the reference fine-tunes pretrained GPT-2, "
                        "neurons/miner.py:60)")
    g.add_argument("--seq-len", dest="seq_len", type=int, default=d.seq_len)
    g.add_argument("--eval-seq-len", dest="eval_seq_len", type=int,
                   default=d.eval_seq_len)
    g.add_argument("--batch-size", dest="batch_size", type=int,
                   default=d.batch_size)
    g.add_argument("--eval-batches", dest="eval_batches", type=int,
                   default=d.eval_batches)
    if role in ("validator", "averager"):
        g.add_argument("--max-delta-abs", dest="max_delta_abs",
                       type=_nonneg_float, default=d.max_delta_abs,
                       help="admission screen: reject submissions whose "
                            "largest |value| exceeds this (crude poisoning "
                            "guard the reference lacks; 0 disables)")
        g.add_argument("--no-accept-quant", dest="accept_quant",
                       action="store_false", default=d.accept_quant,
                       help="fleet is known all-float: reject int8-wire "
                            "submissions instead of dequantizing, and skip "
                            "the quant-template alloc on garbage")
        g.add_argument("--no-wire-v2", dest="accept_wire_v2",
                       action="store_false", default=d.accept_wire_v2,
                       help="refuse v2 shard-manifest submissions (the "
                            "v1-only receiver posture); v2 miners then "
                            "stage as no_delta")
        g.add_argument("--stale-deltas", dest="stale_deltas",
                       choices=("skip", "accept"), default=d.stale_deltas,
                       help="submissions whose rider names a superseded "
                            "base: 'skip' refuses them (averager default "
                            "— merging one re-adds the previous merge's "
                            "update on top of itself), 'accept' is the "
                            "reference's behavior (validator default). "
                            "Riderless submissions are always accepted")
    g.add_argument("--learning-rate", dest="learning_rate", type=float,
                   default=d.learning_rate)
    g.add_argument("--weight-decay", dest="weight_decay", type=float,
                   default=d.weight_decay,
                   help="AdamW decoupled weight decay")
    g.add_argument("--grad-clip", dest="grad_clip", type=float, default=None)
    g.add_argument("--mu-dtype", dest="mu_dtype",
                   choices=("float32", "bfloat16"), default=d.mu_dtype,
                   help="AdamW first-moment storage dtype; bfloat16 halves "
                        "its HBM footprint (7B/8B configs); its "
                        "throughput cost is not measured")
    g.add_argument("--lora-rank", dest="lora_rank", type=int,
                   default=d.lora_rank,
                   help=">0 switches the miner to LoRA-delta training; "
                        "validator/averager accept adapter submissions")
    g.add_argument("--lora-alpha", dest="lora_alpha", type=float,
                   default=d.lora_alpha)
    g.add_argument("--dataset", default=d.dataset, type=_dataset_arg,
                   help="auto | wikitext | synthetic | files:<glob> (local "
                        "text files as the corpus; real data with zero "
                        "egress)")
    g.add_argument("--n-docs", dest="n_docs", type=int, default=d.n_docs,
                   help="document cap for the corpus loader (train split; "
                        "runway for long soaks)")
    g.add_argument("--tokenizer", default=d.tokenizer,
                   help="auto | byte | word (corpus-fit word vocab, "
                        "deterministic per corpus) | bpe (byte-level BPE "
                        "trained locally on the machine's own text — the "
                        "32k real-vocab tokenizer, data/bpe.py) | "
                        "<hf tokenizer name>")
    g.add_argument("--fused-loss", dest="fused_loss", action="store_true",
                   help="compute the LM loss with a tiled head matmul that "
                        "never materializes the [batch, seq, vocab] logits "
                        "(HBM saver; GPT-2, Llama, and LoRA-delta mode)")
    g.add_argument("--accum-steps", dest="accum_steps", type=int,
                   default=d.accum_steps,
                   help="gradient-accumulation microbatches per optimizer "
                        "step (activation memory of batch/N at the same "
                        "effective batch; 7B/8B configs)")
    g.add_argument("--prefetch-depth", dest="prefetch_depth", type=int,
                   default=d.prefetch_depth,
                   help="batches the background input thread keeps ready "
                        "(tokenize+pack ahead of the device; 0 disables, "
                        "the reference's DataLoader-workers equivalent)")
    if role == "miner":  # only the miner publishes raw deltas
        g.add_argument("--delta-dtype", dest="delta_dtype",
                       choices=("float32", "bfloat16", "int8", "sparse8"),
                       default=d.delta_dtype,
                       help="wire dtype of published deltas: bfloat16 "
                            "halves artifact bytes; int8 quarters them "
                            "(per-tensor symmetric scales, rounding error "
                            "<= 1 step per artifact); sparse8 keeps only "
                            "the top-k |values| per tensor int8-quantized "
                            "(~2%% of f32 bytes at the default "
                            "--delta-density — the 7B/8B-config format; "
                            "needs a raw-bytes transport, which all "
                            "built-ins are). Receivers auto-detect every "
                            "form and dequantize at ingest; merges "
                            "accumulate in f32")
        g.add_argument("--delta-density", dest="delta_density", type=float,
                       default=d.delta_density,
                       help="sparse8 kept-coordinate ratio per tensor "
                            "(default 1/64; small tensors <= 4096 elements "
                            "always ship dense)")
        g.add_argument("--wire-v2", dest="wire_v2", action="store_true",
                       default=d.wire_v2,
                       help="publish deltas on the v2 shard-addressed "
                            "wire: top-k + quantized packed per-layer "
                            "form, split into content-addressed shards + "
                            "a small manifest — only CHANGED shards "
                            "upload each push, receivers fetch only "
                            "changed shards, and a miner-side "
                            "error-feedback residual keeps repeated "
                            "lossy publishes from drifting. Receivers "
                            "negotiate v1 fallback via the delta META "
                            "rider, so mixed fleets keep working")
        g.add_argument("--wire-density", dest="wire_density", type=float,
                       default=d.wire_density,
                       help="v2 kept-coordinate ratio per wire tensor "
                            "(default 1/64; tensors <= 4096 elements "
                            "ship dense)")
        g.add_argument("--wire-quant", dest="wire_quant",
                       choices=("int8", "none"), default=d.wire_quant,
                       help="v2 kept-value encoding: int8 (per-tensor "
                            "symmetric scale, 5 bytes/coordinate) or "
                            "none (f32 kept values, 8 bytes/coordinate, "
                            "zero quantization error)")
    g.add_argument("--logits-dtype", dest="logits_dtype",
                   choices=("float32", "bfloat16"), default=d.logits_dtype,
                   help="storage dtype of the [batch, seq, vocab] logits "
                        "buffer (the step's largest activation); MXU "
                        "accumulation stays f32 either way, the loss still "
                        "reduces in f32. bfloat16 halves its HBM round-trips")
    g.add_argument("--remat", dest="remat", action="store_true",
                   default=None,
                   help="jax.checkpoint each transformer block: activation "
                        "HBM of one block instead of the whole stack, one "
                        "extra forward of FLOPs (the 7B/8B configs' knob; "
                        "Llama presets default on, GPT-2 off)")
    g.add_argument("--no-remat", dest="remat", action="store_false",
                   help="force rematerialization OFF (overrides a preset "
                        "that defaults on)")
    g.add_argument("--scan-blocks", dest="scan_blocks", action="store_true",
                   help="trace the transformer stack as one lax.scan'd "
                        "block (~n_layer-fold smaller program, much faster "
                        "XLA compiles on deep models); identical math. "
                        "Wire artifacts (bases, deltas, adapters) stay in "
                        "the universal unrolled layout, so roles can flip "
                        "this independently")

    if role == "server":
        g = p.add_argument_group("serving")
        g.add_argument("--serve-port", dest="serve_port", type=int,
                       default=d.serve_port,
                       help="HTTP generation frontend on "
                            "127.0.0.1:<port>/generate (0 = no HTTP; the "
                            "engine still serves in-process submits)")
        g.add_argument("--serve-slots", dest="serve_slots", type=int,
                       default=d.serve_slots,
                       help="concurrent decode slots (the continuous "
                            "batch width; slot-count buckets ride a "
                            "power-of-two compile ladder)")
        g.add_argument("--page-size", dest="serve_page_size", type=int,
                       default=d.serve_page_size,
                       help="KV-cache page size in tokens (the paging "
                            "granule: sequences own pages, not a "
                            "max-length stripe)")
        g.add_argument("--kv-pages", dest="serve_kv_pages", type=int,
                       default=d.serve_kv_pages,
                       help="total pages in the KV pool (0 = auto: "
                            "slots x pages-per-max-sequence + trash "
                            "page). Undersize deliberately to exercise "
                            "preemption")
        g.add_argument("--max-new-tokens", dest="serve_max_new", type=int,
                       default=d.serve_max_new,
                       help="default generation budget when a request "
                            "does not specify one")
        g.add_argument("--max-seq-len", dest="serve_max_seq", type=int,
                       default=d.serve_max_seq,
                       help="cache capacity per sequence in tokens "
                            "(0 = the model's position cap; rounded "
                            "down to a page multiple)")
        g.add_argument("--max-queue", dest="serve_max_queue", type=int,
                       default=d.serve_max_queue,
                       help="admission bound: past this queue depth the "
                            "HTTP frontend sheds with 429 + Retry-After "
                            "instead of queueing into the latency knee "
                            "(0 = queue without bound)")
        g.add_argument("--no-prefix-cache", dest="serve_prefix_cache",
                       action="store_false",
                       default=d.serve_prefix_cache,
                       help="disable shared-prefix KV page reuse "
                            "(refcounted pages + copy-on-write; on by "
                            "default — common system prompts prefill "
                            "once per server, not once per request)")
        g.add_argument("--speculative", dest="serve_speculative",
                       action="store_true",
                       default=d.serve_speculative,
                       help="speculative decoding: a small fleet-trained "
                            "draft proposes --draft-k tokens per slot per "
                            "step and one batched verify pass scores them "
                            "(provably lossless — output is bit-identical "
                            "to plain decode; off by default)")
        g.add_argument("--no-speculative", dest="serve_speculative",
                       action="store_false",
                       help="force speculative decoding off")
        g.add_argument("--draft-k", dest="serve_draft_k", type=int,
                       default=d.serve_draft_k,
                       help="drafted tokens per slot per speculative "
                            "step (tokens per verify ≈ 1 + accept_rate·K)")
        g.add_argument("--draft-repo", dest="serve_draft_repo",
                       default=d.serve_draft_repo,
                       help="draft base source as 'preset@work_dir' — a "
                            "second transport watching that deployment's "
                            "fleet-averaged revisions feeds the drafter's "
                            "hot-swap lane (empty: self-draft from the "
                            "serving transport, only useful for smoke "
                            "tests)")
        g.add_argument("--no-serve-trace", dest="serve_trace",
                       action="store_false", default=d.serve_trace,
                       help="disable request-scoped stage traces "
                            "(utils/reqtrace.py: per-request lifecycle "
                            "timelines, tail-exemplar freezes into the "
                            "flight recorder, SLO burn-rate feed; on by "
                            "default — host-side only, <2%% overhead)")
        g.add_argument("--trace-exemplars", dest="serve_trace_exemplars",
                       type=int, default=d.serve_trace_exemplars,
                       help="K slowest ttft/tpot requests whose full "
                            "timelines freeze per trace window")
        g.add_argument("--trace-window", dest="serve_trace_window",
                       type=_nonneg_float, default=d.serve_trace_window,
                       help="tail-exemplar reservoir window, seconds")
        g.add_argument("--serve-phase", dest="serve_phase",
                       choices=("unified", "prefill", "decode"),
                       default=d.serve_phase,
                       help="worker class for disaggregated serving "
                            "(engine/kv_transfer.py): 'prefill' runs "
                            "prompt prefill and exports KV pages as "
                            "content-addressed shards, 'decode' adopts "
                            "exported pages and decodes flat-out, "
                            "'unified' (default) does both — the "
                            "router learns the class from /healthz and "
                            "falls back to unified workers whenever a "
                            "class is missing or unhealthy")
        g.add_argument("--swap-policy", dest="swap_policy",
                       choices=("drain", "restart"),
                       default=d.swap_policy,
                       help="base hot-swap policy: 'drain' finishes "
                            "in-flight sequences on the revision they "
                            "started on (admission pauses), 'restart' "
                            "swaps immediately and requeues in-flight "
                            "prompts on the new revision")
        g.add_argument("--swap-poll", dest="swap_poll",
                       type=_nonneg_float, default=d.swap_poll,
                       help="seconds between base-revision probes on "
                            "the watcher thread")

    g = p.add_argument_group("mesh")
    g.add_argument("--dp", type=int, default=d.mesh.dp,
                   help="data-parallel axis; 0 = all visible devices")
    g.add_argument("--fsdp", type=int, default=d.mesh.fsdp)
    g.add_argument("--sp", type=int, default=d.mesh.sp)
    g.add_argument("--tp", type=int, default=d.mesh.tp)
    g.add_argument("--mesh-auto", dest="mesh_auto", action="store_true",
                   help="ignore --dp/--fsdp/--sp/--tp and pick the mesh "
                        "from the model size (dp while the Adam state fits "
                        "replicated, fsdp/tp as it grows)")
    g.add_argument("--dcn-dp", dest="dcn_dp", type=int, default=d.mesh.dcn_dp,
                   help="outermost dp groups that cross the slow network "
                        "(multi-slice DCN); keeps fsdp/sp/tp and the rest "
                        "of dp on ICI")
    g.add_argument("--multihost-coordinator", dest="multihost_coordinator",
                   default=None, metavar="HOST:PORT",
                   help="explicit jax.distributed coordinator for manual "
                        "(non-GCE) topologies; TPU pods auto-detect")
    g.add_argument("--multihost-processes", dest="multihost_processes",
                   type=int, default=None)
    g.add_argument("--multihost-id", dest="multihost_id", type=int,
                   default=None)

    g = p.add_argument_group("cadence")
    g.add_argument("--send-interval", dest="send_interval", type=float,
                   default=d.send_interval)
    if role == "miner":  # only the miner runs the publication pipeline
        g.add_argument("--push-async", dest="push_async",
                       action="store_true", default=d.push_async,
                       help="overlap delta publication (device->host "
                            "transfer, serialization, upload, meta rider) "
                            "and checkpoint I/O with training compute on a "
                            "background worker; an in-flight push is "
                            "superseded by the next interval's, never "
                            "queued behind (default on)")
        g.add_argument("--no-push-async", dest="push_async",
                       action="store_false",
                       help="restore the fully sequential publish path "
                            "(the reference's blocking upload semantics)")
        g.add_argument("--push-queue-depth", dest="push_queue_depth",
                       type=int, default=d.push_queue_depth,
                       help="pushes the publisher may hold pending before "
                            "the oldest is superseded (each artifact is "
                            "the whole cumulative delta, so >1 only delays "
                            "supersession; default 1)")
    g.add_argument("--self-eval-interval", dest="self_eval_interval",
                   type=float, default=d.self_eval_interval,
                   help="miner self-validation cadence in seconds; -1 = "
                        "follow --send-interval, 0 = disable the guard")
    g.add_argument("--self-eval-patience", dest="self_eval_patience",
                   type=int, default=d.self_eval_patience)
    g.add_argument("--self-eval-margin", dest="self_eval_margin",
                   type=float, default=d.self_eval_margin,
                   help="held-out loss may exceed the best-seen by this "
                        "much before an eval counts as a strike")
    g.add_argument("--keep-optimizer-on-pull",
                   dest="keep_optimizer_on_pull", action="store_true",
                   default=d.keep_optimizer_on_pull,
                   help="carry Adam moments across base pulls instead of "
                        "the reference's reset — removes the per-pull "
                        "warmup transient on short merge cadences")
    if role == "miner":  # only the miner wires a CheckpointStore today
        g.add_argument("--checkpoint-interval", dest="checkpoint_interval",
                       type=float, default=d.checkpoint_interval,
                       help="seconds between local Orbax checkpoints; "
                            "0 disables")
        g.add_argument("--checkpoint-dir", dest="checkpoint_dir",
                       default=None,
                       help="default: <work_dir>/checkpoints/<hotkey>")
    g.add_argument("--check-update-interval", dest="check_update_interval",
                   type=float, default=d.check_update_interval)
    g.add_argument("--validation-interval", dest="validation_interval",
                   type=float, default=d.validation_interval)
    g.add_argument("--val-cohort", dest="val_cohort", type=int,
                   default=d.val_cohort,
                   help="miner deltas scored per batched eval pass "
                        "(engine/batched_eval.py); <=1 restores the "
                        "sequential per-miner path")
    g.add_argument("--val-pipeline-depth", dest="val_pipeline_depth",
                   type=int, default=d.val_pipeline_depth,
                   help="cohorts staged (fetched+screened) ahead of device "
                        "eval; 0 disables the fetch/eval overlap")
    g.add_argument("--averaging-interval", dest="averaging_interval",
                   type=float, default=d.averaging_interval)
    if role in ("validator", "averager"):  # the delta-consuming roles
        g = p.add_argument_group("ingest")
        g.add_argument("--ingest-workers", dest="ingest_workers", type=int,
                       default=d.ingest_workers,
                       help="concurrent artifact fetches during delta "
                            "ingest (engine/ingest.py); 1 restores serial "
                            "fetch order")
        g.add_argument("--ingest-cache-mb", dest="ingest_cache_mb",
                       type=int, default=d.ingest_cache_mb,
                       help="byte budget (MB) of the content-addressed "
                            "host cache keyed (hotkey, delta_revision): "
                            "unchanged submissions skip download + decode "
                            "+ dequantize + screen entirely; 0 disables "
                            "(re-download every round, reference behavior)")

    if role == "averager":
        g = p.add_argument_group("strategy")
        g.add_argument("--strategy",
                       choices=("weighted", "parameterized", "genetic"),
                       default=d.strategy)
        g.add_argument("--merge-chunk", dest="merge_chunk", type=int,
                       default=d.merge_chunk,
                       help="deltas stacked on-device at a time in the "
                            "weighted merge (device memory stays "
                            "chunk x params however many miners submit)")
        g.add_argument("--meta-epochs", dest="meta_epochs", type=int,
                       default=d.meta_epochs)
        g.add_argument("--outer-momentum", dest="outer_momentum", type=float,
                       default=d.outer_momentum,
                       help=">0 applies a DiLoCo-style outer Nesterov "
                            "momentum step over the merged delta")
        g.add_argument("--outer-lr", dest="outer_lr", type=float,
                       default=d.outer_lr)
        g.add_argument("--meta-lr", dest="meta_lr", type=float,
                       default=d.meta_lr)
        g.add_argument("--meta-optimizer", dest="meta_optimizer",
                       choices=("adam", "sgd"), default=d.meta_optimizer,
                       help="meta-learning optimizer for the merge "
                            "weights; sgd is the reference's spelling, "
                            "adam actually separates the weights")
        g.add_argument("--genetic-population", dest="genetic_population",
                       type=int, default=d.genetic_population)
        g.add_argument("--genetic-generations", dest="genetic_generations",
                       type=int, default=d.genetic_generations)
        g.add_argument("--publish-policy", dest="publish_policy",
                       choices=("improved", "always"),
                       default=d.publish_policy,
                       help="'improved' (default) publishes the merged "
                            "base only when it does not worsen the current "
                            "base's eval loss (one extra eval pass; keeps "
                            "the shared base monotone under noisy/short "
                            "miner deltas); 'always' is the reference's "
                            "publish-regardless behavior")
        g.add_argument("--genetic-screen-batches",
                       dest="genetic_screen_batches", type=int,
                       default=d.genetic_screen_batches,
                       help="successive-halving fitness: rank candidates "
                            "on this many val batches, full passes only "
                            "for elites (0 = the reference's full-set "
                            "fitness for every candidate)")
        g.add_argument("--genetic-sigma", dest="genetic_sigma", type=float,
                       default=d.genetic_sigma)

        g = p.add_argument_group("hierarchy")
        g.add_argument("--hier", choices=("", "sub", "root"),
                       default=d.hier,
                       help="tree aggregation (engine/hier_average.py): "
                            "'sub' gathers a plan_fanout slice of the "
                            "fleet and publishes its partial aggregate "
                            "under __agg__.<node>; 'root' merges the "
                            "configured sub nodes' aggregates into the "
                            "base; '' is the flat reference topology")
        g.add_argument("--hier-node", dest="hier_node", default=d.hier_node,
                       help="this sub-averager's stable node id "
                            "(default: --hotkey); names its __agg__ "
                            "artifact and its subavg.<node> lease")
        g.add_argument("--hier-nodes", dest="hier_nodes",
                       default=d.hier_nodes,
                       help="comma-separated sub node ids — the root's "
                            "gather list AND every sub's shared "
                            "plan_fanout keyspace (the stable production "
                            "spelling)")
        g.add_argument("--hier-fanout", dest="hier_fanout", type=int,
                       default=d.hier_fanout,
                       help="miners per sub-averager when no --hier-nodes "
                            "list is given: nodes auto-name "
                            "sub0..subN-1, N = ceil(miners / fanout)")
        g.add_argument("--hier-wire-v2", dest="hier_wire_v2",
                       action="store_true", default=d.hier_wire_v2,
                       help="publish partial aggregates on the v2 shard "
                            "wire (density 1.0 + quant none — lossless; "
                            "unchanged aggregate layers dedupe at shard "
                            "granularity)")

    g = p.add_argument_group("resilience")
    if role in ("validator", "averager"):  # the monitor roles act on SLOs
        g.add_argument("--remediate", dest="remediate", action="store_true",
                       default=d.remediate,
                       help="act on SLO breaches (engine/remediate.py): "
                            "quarantine breaching miners out of the ingest "
                            "set (probation re-admission after clean "
                            "heartbeats), decay their scores, and size "
                            "cohorts down the compiled-bucket ladder; "
                            "needs --heartbeat-interval > 0")
        g.add_argument("--quarantine-rules", dest="quarantine_rules",
                       default=d.quarantine_rules,
                       help="comma-separated SLO rule NAMES whose breach "
                            "quarantines a miner")
        g.add_argument("--probation-beats", dest="probation_beats",
                       type=int, default=d.probation_beats,
                       help="fresh clean heartbeats before a quarantined "
                            "miner re-admits into probation")
        g.add_argument("--probation-rounds", dest="probation_rounds",
                       type=int, default=d.probation_rounds,
                       help="rounds a re-admitted miner stays on "
                            "probation (a breach there re-quarantines)")
        g.add_argument("--score-decay", dest="score_decay", type=float,
                       default=d.score_decay,
                       help="multiplier applied to a quarantined miner's "
                            "score each round")
    if role == "averager":
        g.add_argument("--standby", dest="standby", action="store_true",
                       default=d.standby,
                       help="start as a PASSIVE failover averager: follow "
                            "the primary's lease/heartbeat/base revision "
                            "and take over publication (lease epoch + 1) "
                            "only after --failover-deadline of silence")
        g.add_argument("--failover-deadline", dest="failover_deadline",
                       type=_nonneg_float, default=d.failover_deadline,
                       help="seconds of primary silence before a standby "
                            "takes over (0 = 3x --averaging-interval)")
    g.add_argument("--chaos-spec", dest="chaos_spec", default=None,
                   help="JSON transport/chaos.py ChaosSpec wrapping this "
                        "role's transport (deterministic fault injection "
                        "for soaks/tests; NEVER set in production), e.g. "
                        "'{\"fetch_error_rate\": 0.1, \"seed\": 7}'")

    g = p.add_argument_group("run bounds")
    g.add_argument("--max-steps", dest="max_steps", type=int, default=None)
    g.add_argument("--rounds", type=int, default=None)

    g = p.add_argument_group("observability")
    g.add_argument("--metrics-path", dest="metrics_path", default=None)
    g.add_argument("--metrics-rotate-mb", dest="metrics_rotate_mb",
                   type=int, default=d.metrics_rotate_mb,
                   help="rotate the --metrics-path JSONL once it exceeds "
                        "this many MB (0 = never; soak runs otherwise grow "
                        "one multi-GB file). obs_report/fleet_report read "
                        "rotated segments transparently")
    g.add_argument("--metrics-keep-segments", dest="metrics_keep_segments",
                   type=int, default=d.metrics_keep_segments,
                   help="rotated segments kept per metrics file")
    g.add_argument("--heartbeat-interval", dest="heartbeat_interval",
                   type=_nonneg_float, default=d.heartbeat_interval,
                   help="fleet health plane (engine/health.py): publish a "
                        "versioned heartbeat through the transport every N "
                        "seconds; validator/averager also aggregate the "
                        "fleet's heartbeats into the contribution ledger "
                        "and evaluate SLO rules. 0 disables")
    g.add_argument("--obs-port", dest="obs_port", type=int,
                   default=d.obs_port,
                   help="serve Prometheus-text metrics (obs registry + "
                        "fleet ledger) on 127.0.0.1:<port>/metrics, plus "
                        "the /debug/dump, /debug/profile?ms=N and "
                        "/debug/stacks postmortem endpoints; 0 disables")
    g.add_argument("--no-devprof", dest="devprof", action="store_false",
                   default=d.devprof,
                   help="disable the device performance observatory "
                        "(utils/devprof.py): per-program FLOPs/bytes "
                        "cost attribution, exec histograms, and roofline "
                        "achieved-fraction gauges")
    g.add_argument("--no-lineage", dest="lineage", action="store_false",
                   default=d.lineage,
                   help="disable the provenance plane (engine/lineage"
                        ".py): per-merge content-addressed __lineage__ "
                        "records (parent revision + exact contribution "
                        "set and weights, replay-auditable via "
                        "scripts/lineage_report.py) and the merged-"
                        "quality EWMA/CUSUM drift detector")
    g.add_argument("--flight-events", dest="flight_events", type=int,
                   default=d.flight_events,
                   help="flight-recorder ring capacity (utils/flight.py): "
                        "recent spans/SLO fires/lease flips/publish "
                        "outcomes kept in memory and frozen into a "
                        "transport-published __pm__ postmortem bundle on "
                        "SLO breach, remediation, or crash; 0 disables")
    if role == "miner":
        g.add_argument("--log-every", dest="log_every", type=int,
                       default=d.log_every,
                       help="train steps between metric-sink logs (each log "
                            "syncs the device loss to the host)")
    g.add_argument("--mlflow-uri", dest="mlflow_uri", default=None)
    if role == "miner":  # only the miner's train loop ticks TraceCapture
        g.add_argument("--profile-dir", dest="profile_dir", default=None,
                       help="capture a jax.profiler trace of a few "
                            "post-warmup train steps into this directory "
                            "(TensorBoard/xprof-readable), then continue "
                            "at full speed")
        g.add_argument("--profile-steps", dest="profile_steps", type=int,
                       default=d.profile_steps)
        g.add_argument("--no-anomaly-trace", dest="anomaly_trace",
                       action="store_false", default=d.anomaly_trace,
                       help="disable the anomaly-armed profiler capture "
                            "(a loss spike, push-failure streak, or "
                            "step-time p99 blowout otherwise records one "
                            "bounded jax.profiler trace automatically)")
        g.add_argument("--anomaly-dir", dest="anomaly_dir", default=None,
                       help="trace directory for the anomaly capture; "
                            "default <work-dir>/anomaly_traces/<hotkey>")
    return p
