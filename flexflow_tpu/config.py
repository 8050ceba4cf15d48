"""Runtime configuration & flag system.

TPU-native analog of the reference's ``FFConfig`` (``include/flexflow/config.h:92-160``,
parsed in ``src/runtime/model.cc:3566-3730``). Instead of querying Legion/Realm for
nodes/GPUs, we query ``jax.devices()``; ``-ll:gpu`` becomes ``--tpus-per-node`` /
the ambient device count. All reference flags are accepted (same spellings) so
reference launch scripts port over directly.
"""
from __future__ import annotations

import dataclasses
import logging
import sys
from typing import List, Optional, Sequence


# Reference flags (``model.cc:3566-3730``) that select nothing here: the
# parser takes them, and the value of those that carry one, so that a
# script written for the reference still starts, and sets nothing.
_IGNORED_FLAGS = frozenset({
    "--enable-parameter-parallel", "--enable-attribute-parallel",
    "--enable-sample-parallel", "--enable-propagation",
    "--enable-inplace-optimizations", "--overlap", "--fusion",
    "--include-costs-dot-graph"})
_IGNORED_VALUE_FLAGS = frozenset({
    "-d", "--dataset", "--search-num-nodes", "--search-num-workers",
    "--simulator-workspace-size", "--compgraph", "-ll:tpu", "-ll:gpu",
    "-ll:cpu"})


@dataclasses.dataclass
class FFConfig:
    # -------- training (reference: -e/-b/--lr/--wd/-p/-d) --------
    epochs: int = 1
    batch_size: int = 64
    learning_rate: float = 0.01
    weight_decay: float = 1e-4
    print_freq: int = 10
    # -------- machine --------
    num_nodes: int = 1
    # multi-host rendezvous (reference: GASNet/mpirun launch, MULTI-NODE.md;
    # here: jax.distributed — see parallel/distributed.py). Empty = also
    # honor FF_COORDINATOR_ADDRESS / FF_NUM_PROCESSES / FF_PROCESS_ID env.
    coordinator_address: str = ""
    process_id: int = -1
    # multi-process failure detection (resilience/coord.py): per-rank
    # heartbeat cadence, how long a silent peer is tolerated, and the
    # bound on every cross-rank rendezvous (checkpoint commit barriers,
    # recovery re-rendezvous). 0 = keep the coordinator defaults; the
    # FF_HB_INTERVAL_S / FF_HB_TIMEOUT_S / FF_BARRIER_TIMEOUT_S env vars
    # override both. Every wait is bounded — a timeout raises
    # RankFailure with the suspected rank attributed.
    heartbeat_interval_s: float = 0.0
    heartbeat_timeout_s: float = 0.0
    barrier_timeout_s: float = 0.0
    # memory per device in MB (reference -ll:fsize); used by memory-aware search
    device_mem_mb: int = 0        # 0 = query from device / default model
    # -------- search (reference --budget/--alpha/...) --------
    search_budget: int = -1
    search_alpha: float = 1.2
    only_data_parallel: bool = False
    base_optimize_threshold: int = 10
    enable_memory_search: bool = False
    search_algo: str = "unity"    # "unity" (substitution DP) | "mcmc" | "dp"
    substitution_json_path: Optional[str] = None
    # -------- simulator --------
    machine_model_version: int = 0
    machine_model_file: str = ""
    simulator_segment_size: int = 16777216
    simulator_max_num_segments: int = 1
    # measurement-grounded cost-model calibration v2 (host dispatch/
    # memory-bandwidth/parallel-efficiency terms + persisted collective
    # tables, search/calibration.py). "auto" honors FF_CALIBRATION_V2.
    calibration_v2: str = "auto"  # "auto" | "true" | "false"
    # hierarchical topology-aware placement (parallel/placement.py,
    # arXiv 2110.10548): the search assigns mesh axes to hardware tiers
    # (ici/host/dcn) and picks a reduction-tree shape per collective.
    # "auto" enables it whenever the machine has more than one tier
    # (multi-slice / multi-host); single-tier machines are unaffected
    # either way. FF_HIER_PLACEMENT=0 is the env override.
    hier_placement: str = "auto"  # "auto" | "true" | "false"
    # -------- observability (obs/) --------
    # span/counter tracing (obs/events.py): "true"/"false" force the
    # PROCESS-WIDE recorder on/off at compile (one recorder per
    # process — "false" also stops tracing of other models/servers in
    # it); "auto" (default) honors the FF_TRACE env var so recorded
    # benchmarks are unchanged unless asked. Near-zero-cost when
    # disabled: one flag check a site, and every PR's end-to-end numbers
    # are the driver's untraced runs through those sites.
    trace: str = "auto"           # "auto" | "true" | "false"
    # write a Chrome trace-event JSON (Perfetto/TensorBoard-viewable)
    # of the recorded spans here when fit() completes; "" = off
    trace_export_file: str = ""
    # step-time attribution (obs/attribution.py): profile a few
    # steady-state steps of the compiled plan when training completes
    # and write a MEASURED per-op/per-collective cost side into the
    # strategy audit record next to the predicted ones, then run the
    # cost-model drift detector (obs/drift.py) over the pair. "auto"
    # honors FF_ATTRIB; enabling implies tracing (the audit record the
    # measured side lands in only exists when tracing is on). Adds no
    # per-step work — the harness runs once, after the last epoch.
    attribution: str = "auto"     # "auto" | "true" | "false"
    # steady-state steps the attribution harness profiles
    # (FF_ATTRIB_STEPS overrides)
    attribution_steps: int = 3
    # -------- execution --------
    allow_tensor_op_math_conversion: bool = True   # = allow bf16 matmul accum
    profiling: bool = False
    # static plan verification (analysis/plan_verifier.py): compile
    # proves the adopted strategy executable — mesh-axis soundness,
    # shard divisibility, legal reshard lowerings at every layout seam,
    # a static peak-memory envelope, and SPMD collective-ordering
    # consistency — BEFORE params materialize; failures raise a typed
    # PlanVerificationError with op/seam attribution. FF_PLAN_VERIFY=0
    # (or this flag) disables the gate; findings land in the strategy
    # audit record and the ff_plan_verify_* counters either way.
    plan_verify: bool = True
    # -------- strategy import/export --------
    export_strategy_file: str = ""
    import_strategy_file: str = ""
    export_strategy_task_graph_file: str = ""
    # -------- TPU-native --------
    mesh_shape: Optional[Sequence[int]] = None     # explicit ICI mesh, else auto
    # pipeline parallelism through the product path (reference reserves
    # OP_PIPELINE, ffconst.h:159, with no implementation): partition the
    # maximal repeated-block region into this many GPipe stages
    pipeline_stages: int = 1
    pipeline_microbatches: int = 0                 # 0 = 2 * stages
    # interleaved (circular) schedule: chunks per stage (1 = plain GPipe;
    # v > 1 cuts the pipeline bubble to (S-1)/(M*v))
    pipeline_chunks: int = 1
    # Megatron-style tensor parallelism INSIDE each pipeline stage
    # (dp x pp x tp composition; the reference composes per-op machine
    # views the same way, substitution.cc:1898)
    pipeline_tp: int = 1
    # direct dp x tp (x sp) preset WITHOUT a pipeline or a search:
    # --tp N applies transformer_strategy (Megatron column/row sharding
    # over a size-N mesh axis); --sp additionally shards the sequence
    # dim (ring/Ulysses-style context parallelism via GSPMD)
    tensor_parallel: int = 1
    sequence_parallel: bool = False
    # ZeRO-1: shard optimizer moments over the replicated mesh axes
    # (runtime/zero.py); the reference keeps full state per replica.
    # This is the legacy UNIFORM flag (every shardable leaf, no
    # scoring) — pinned bit-identical across releases.
    shard_optimizer_states: bool = False
    # per-parameter ZeRO in the search space (search/zero_plan.py,
    # arXiv 2004.13336): the cost model scores each parameter's update
    # path (replicated all-reduce vs reduce-scatter + sharded update +
    # all-gather over the placed tier path) and the stack honors the
    # per-parameter assignment end to end (strategy serialization,
    # plan verifier, executor state pins, checkpoint meta).
    #   "off"    — never plan (default);
    #   "auto"   — shard the predicted-free parameters, plus whatever
    #              the device-memory envelope needs;
    #   "memory" — shard only what the envelope needs to fit;
    #   "all"    — shard everything shardable (the uniform assignment,
    #              scored and audited).
    zero_policy: str = "off"
    # "auto" slack: a parameter shards when its predicted marginal
    # collective overhead is within this fraction of its replicated
    # update cost
    zero_overhead_frac: float = 0.05
    # communication–computation overlap (runtime/overlap.py): lower
    # gradient sync as size-bucketed groups whose optimizer updates
    # launch as each bucket's backward slice completes (barrier-chained
    # dependency cuts — bit-exact with the serial path by construction),
    # prefetch ZeRO param gathers one bucket ahead, and pipeline
    # tier-staged reshard legs. Also flips the cost model into
    # overlap-aware scoring (exposed-vs-hidden sync). "auto" honors the
    # FF_OVERLAP env var and resolves OFF when unset — the serial path
    # stays the bit-exact default. See docs/performance.md.
    overlap: str = "auto"         # "auto" | "on" | "off"
    # gradient-bucket size for the overlap schedule (MiB, fractional
    # allowed): consecutive reverse-order layers coalesce until this
    # many gradient bytes accumulate; a single larger parameter gets
    # its own bucket
    overlap_bucket_mb: float = 4.0
    # ZeRO all-gather prefetch depth under overlap: >= 1 chains each
    # bucket's updated (re-gathered) params into the next bucket's
    # launch token so the gather is scheduled one bucket ahead of use;
    # 0 chains raw grads only (gathers may sink to the step end)
    zero_prefetch: int = 1
    # quantized gradient collectives (ops/quantized_collectives.py,
    # arXiv 2506.17615): int8/fp8 wire payloads with per-chunk scaling
    # and error feedback, planned per-tensor (flat grad sync) and
    # per-phase (PR 9's reduction trees — quantize the DCN leg, keep
    # ICI legs full-precision), scored by the calibrated cost model.
    #   "off"      — plan nothing (default; the bit-exact path — but a
    #                strategy IMPORTED with a qsync plan is still
    #                honored verbatim, like zero/overlap);
    #   "auto"     — quantize where the model predicts a win;
    #   "dcn_only" — quantize only inter-slice (DCN) legs;
    #   "all"      — quantize every eligible leg;
    #   "disable"  — force full precision even for an imported plan
    #                (what --no-quantized-collectives parses to).
    # FF_QUANTIZED_COLLECTIVES overrides when set (an explicit off
    # value there also strips imported plans). Replicated-math seams
    # (sharded weights, per-op collectives) always stay full-precision
    # — the structural accuracy-risk gate.
    quantized_collectives: str = "off"
    # wire dtype for quantized legs: "int8" (default) |
    # "float8_e4m3" | "float8_e5m2" (FF_QSYNC_WIRE overrides; fp8
    # falls back to int8 when the installed jax lacks the dtype)
    qsync_wire: str = "int8"
    # rematerialization: "none" | "blocks" (jax.checkpoint around each
    # repeated block — HBM-for-FLOPs; executor._emit_remat)
    remat: str = "none"
    # micro-batch gradient accumulation (one optimizer update per
    # `gradient_accumulation_steps` micro-batches; batch_size must divide)
    gradient_accumulation_steps: int = 1
    # let the search score a pipeline candidate (bubble model) against the
    # searched sharding strategy and pick the winner
    enable_pipeline_search: bool = False
    # ragged pipeline schedule (parallel/pipeline_lowering.py): "auto"
    # falls back to unequal per-stage block counts with embedding/head
    # absorbed into the edge stages when the uniform region finder
    # fails; "force" always uses the ragged finder; "off" disables.
    pipeline_ragged: str = "auto"
    # per-op concurrent device-subset placement (parallel/banks.py): the
    # search may place groups of independent same-signature ops (DLRM
    # embedding banks) on disjoint device subsets when the cost model
    # predicts a win (reference MachineView placement). "auto" proposes
    # when profitable; "off" disables; "force" banks every eligible group.
    banked_placement: str = "auto"
    use_bf16_compute: bool = True                  # matmuls in bf16, fp32 accum
    # end-to-end bf16 ACTIVATIONS: inter-op tensors are stored bf16
    # (halves HBM traffic on the memory-bound segments); weights stay
    # fp32 masters, losses/norms still reduce in fp32 internally.
    # Off by default — enable for MFU on bandwidth-bound models.
    bf16_activations: bool = False
    # async-dispatch training loop (runtime/metrics_buffer.py): how many
    # train steps the host may keep in flight before blocking on the
    # step leaving the window; per-step metrics stay device-resident
    # and are fetched in one device_get at print_freq/epoch boundaries.
    # <= 0 forces the sync-every-step fallback (also FF_SYNC_EVERY_STEP=1
    # / --sync-every-step) — fetch and NaN-screen every step, for
    # debugging. See docs/performance.md.
    async_dispatch_steps: int = 8
    # dataloader prefetch depth (runtime/dataloader.py): device batches
    # dispatched ahead of consumption; 0 disables, 1 is the old
    # single-slot double-buffer
    prefetch_batches: int = 2
    # forced kernel implementations (kernels/registry.py): "auto"
    # leaves each op to choose its kernel from its shapes;
    # "<op>:<impl>[,...]" forces choices (e.g. "attention:ring").
    # FF_KERNEL_IMPL env and --kernel-impl override. A forced impl that
    # is not available on the mesh/shapes is a typed compile-time error
    # naming the op.
    kernel_impls: str = "auto"
    # sequence-parallel (context) mesh axis degree: N >= 2 carves a
    # dedicated "seq" axis out of the device factorization; attention
    # ops assigned the `ring` impl shard the context dimension over it
    # (kernels/ring_attention.py lowered as one shard_map with ppermute
    # ring hops). 0/1 = no seq axis. Unlike --sp (the GSPMD tp preset),
    # this axis is reserved for ring attention — the general search
    # never shards batch/params over it.
    seq_parallel_degree: int = 0
    # measured DP-floor guard on search adoption: after the search picks a
    # strategy, compile+time a few real steps of it AND of plain data
    # parallel, and keep DP when the searched program measures slower (the
    # reference trusts its calibrated simulator, simulator.cc:537; we
    # enforce the floor by measurement). "auto" = on when running on a
    # real accelerator, off on the CPU simulator (double-compile is
    # expensive there and tests exercise the guard explicitly).
    search_floor_guard: str = "auto"   # "auto" | "true" | "false"
    floor_guard_steps: int = 3
    # -------- serving plans (search/serving_plan.py) --------
    # batch classes the serving search targets, csv ("1,4,16,64");
    # "" = the InferenceSession defaults. One plan is searched per
    # bucket (mode="serving" of optimize_strategy).
    serving_buckets: str = ""
    # KV-cache sequence envelope the serving plans budget for;
    # 0 = the graph's compile-time sequence length
    serving_max_seq: int = 0
    # decode weight of the serving objective (prefill +
    # decode_tokens x decode-step latency); 0 = serving_max_seq
    serving_decode_tokens: int = 0
    # serving-plan artifact for ModelRepository load paths (a strategy
    # JSON with a "serving" block; see docs/serving.md)
    serving_strategy_file: str = ""
    # measured decode floor on serving-plan adoption (the serving
    # analog of search_floor_guard): per bucket, the imported
    # sub-strategy is kept only if its measured decode-step latency
    # beats the no-serving-plan baseline's — a mispredicting serving
    # cost model can never ship a per-bucket plan that decodes slower
    # than the plan it replaces. "auto" = on off-CPU backends only.
    serving_floor_guard: str = "auto"  # "auto" | "true" | "false"
    seed: int = 0

    def serving_buckets_list(self) -> List[int]:
        """Parsed ``serving_buckets`` ([] = caller defaults)."""
        if not self.serving_buckets:
            return []
        return sorted({int(b) for b in
                       str(self.serving_buckets).split(",") if b})

    @property
    def seq_length(self) -> int:  # reference FFIterationConfig::seq_length
        return getattr(self, "_seq_length", -1)

    # ------------------------------------------------------------------
    @classmethod
    def parse_args(cls, argv: Optional[List[str]] = None) -> "FFConfig":
        """Parse reference-compatible command-line flags.

        Mirrors ``FFConfig::parse_args`` (reference ``model.cc:3566-3730``).
        Unknown flags are ignored (the reference forwards them to Legion).
        """
        cfg = cls()
        args = list(sys.argv[1:] if argv is None else argv)
        ignored: List[str] = []
        i = 0

        def take() -> str:
            nonlocal i
            i += 1
            return args[i]

        while i < len(args):
            a = args[i]
            if a in ("-e", "--epochs"):
                cfg.epochs = int(take())
            elif a in ("-b", "--batch-size"):
                cfg.batch_size = int(take())
            elif a == "--lr" or a == "--learning-rate":
                cfg.learning_rate = float(take())
            elif a == "--wd" or a == "--weight-decay":
                cfg.weight_decay = float(take())
            elif a in ("-p", "--print-freq"):
                cfg.print_freq = int(take())
            elif a == "--budget" or a == "--search-budget":
                cfg.search_budget = int(take())
            elif a == "--alpha" or a == "--search-alpha":
                cfg.search_alpha = float(take())
            elif a == "--only-data-parallel":
                cfg.only_data_parallel = True
            elif a == "--no-plan-verify":
                cfg.plan_verify = False
            elif a == "--base-optimize-threshold":
                cfg.base_optimize_threshold = int(take())
            elif a == "--memory-search":
                cfg.enable_memory_search = True
            elif a == "--search-algo":
                cfg.search_algo = take()
            elif a == "--substitution-json":
                cfg.substitution_json_path = take()
            elif a == "--floor-guard":
                cfg.search_floor_guard = take().lower()
            elif a == "--no-floor-guard":
                cfg.search_floor_guard = "false"
            elif a == "--machine-model-version":
                cfg.machine_model_version = int(take())
            elif a == "--machine-model-file":
                cfg.machine_model_file = take()
            elif a == "--simulator-segment-size":
                cfg.simulator_segment_size = int(take())
            elif a == "--simulator-max-num-segments":
                cfg.simulator_max_num_segments = int(take())
            elif a == "--calibration-v2":
                cfg.calibration_v2 = take().lower()
            elif a == "--hier-placement":
                cfg.hier_placement = take().lower()
            elif a == "--no-hier-placement":
                cfg.hier_placement = "false"
            elif a == "--trace":
                cfg.trace = "true"
            elif a == "--no-trace":
                cfg.trace = "false"
            elif a == "--trace-export":
                cfg.trace_export_file = take()
                cfg.trace = "true"
            elif a == "--attribution":
                cfg.attribution = "true"
            elif a == "--no-attribution":
                cfg.attribution = "false"
            elif a == "--attribution-steps":
                cfg.attribution_steps = int(take())
            elif a == "--profiling":
                cfg.profiling = True
            elif a == "--allow-tensor-op-math-conversion":
                cfg.allow_tensor_op_math_conversion = True
                cfg.use_bf16_compute = True   # symmetric with --f32-compute
            elif a in ("--no-tensor-op-math-conversion", "--f32-compute"):
                # TPU-native default is bf16 matmul compute (the MXU's
                # native dtype) — unlike the reference, which defaults its
                # TF32/FP16 conversion OFF (model.cc:3491). This flag
                # restores full-f32 math for numerics debugging.
                cfg.allow_tensor_op_math_conversion = False
                cfg.use_bf16_compute = False
            elif a == "--export" or a == "--export-strategy":
                cfg.export_strategy_file = take()
            elif a == "--import" or a == "--import-strategy":
                cfg.import_strategy_file = take()
            elif a == "--taskgraph":
                cfg.export_strategy_task_graph_file = take()
            elif a == "-ll:fsize":
                cfg.device_mem_mb = int(take())
            elif a == "--nodes":
                cfg.num_nodes = int(take())
            elif a == "--coordinator-address":
                cfg.coordinator_address = take()
            elif a == "--process-id":
                cfg.process_id = int(take())
            elif a == "--mesh-shape":
                cfg.mesh_shape = tuple(int(x) for x in take().split("x"))
            elif a in ("--pp", "--pipeline-stages"):
                cfg.pipeline_stages = int(take())
            elif a in ("--num-microbatches", "--pipeline-microbatches"):
                cfg.pipeline_microbatches = int(take())
            elif a in ("--pipeline-chunks", "--interleave"):
                cfg.pipeline_chunks = int(take())
            elif a in ("--pp-tp", "--pipeline-tp"):
                cfg.pipeline_tp = int(take())
            elif a in ("--tp", "--tensor-parallel"):
                cfg.tensor_parallel = int(take())
            elif a in ("--sp", "--sequence-parallel"):
                cfg.sequence_parallel = True
            elif a == "--seq-parallel":
                cfg.seq_parallel_degree = int(take())
            elif a == "--kernel-impl":
                # repeated flags accumulate (the later pair of one op
                # wins in kernels/registry.parse_forced)
                v = take()
                cfg.kernel_impls = v if cfg.kernel_impls == "auto" \
                    else f"{cfg.kernel_impls},{v}"
            elif a == "--bf16-activations":
                cfg.bf16_activations = True
            elif a in ("--zero", "--shard-optimizer-states"):
                cfg.shard_optimizer_states = True
            elif a == "--zero-policy":
                cfg.zero_policy = take().lower()
            elif a == "--zero-search":
                cfg.zero_policy = "auto"
            elif a == "--zero-overhead-frac":
                cfg.zero_overhead_frac = float(take())
            elif a == "--overlap-schedule":
                cfg.overlap = take().lower()
            elif a == "--no-overlap-schedule":
                cfg.overlap = "off"
            elif a == "--overlap-bucket-mb":
                cfg.overlap_bucket_mb = float(take())
            elif a == "--zero-prefetch":
                cfg.zero_prefetch = int(take())
            elif a == "--quantized-collectives":
                cfg.quantized_collectives = take().lower()
            elif a == "--no-quantized-collectives":
                # "disable", not "off": strips an imported strategy's
                # qsync plan too (the explicit full-precision A/B knob)
                cfg.quantized_collectives = "disable"
            elif a == "--qsync-wire":
                cfg.qsync_wire = take().lower()
            elif a == "--remat":
                cfg.remat = "blocks"
            elif a in ("--gradient-accumulation-steps", "--accum"):
                cfg.gradient_accumulation_steps = int(take())
            elif a == "--enable-pipeline-search":
                cfg.enable_pipeline_search = True
            elif a == "--banked-placement":
                cfg.banked_placement = take()
            elif a == "--pipeline-ragged":
                cfg.pipeline_ragged = take()
            elif a == "--async-dispatch-steps":
                cfg.async_dispatch_steps = int(take())
            elif a == "--sync-every-step":
                cfg.async_dispatch_steps = 0
            elif a == "--prefetch-batches":
                cfg.prefetch_batches = int(take())
            elif a == "--serving-buckets":
                cfg.serving_buckets = take()
            elif a == "--serving-max-seq":
                cfg.serving_max_seq = int(take())
            elif a == "--serving-decode-tokens":
                cfg.serving_decode_tokens = int(take())
            elif a == "--serving-strategy":
                cfg.serving_strategy_file = take()
            elif a == "--serving-floor-guard":
                cfg.serving_floor_guard = take()
            elif a == "--seed":
                cfg.seed = int(take())
            elif a in _IGNORED_FLAGS:
                ignored.append(a)
            elif a in _IGNORED_VALUE_FLAGS:
                ignored.append(f"{a} {take()}")
            # unknown flags: skip (reference forwards to Legion)
            i += 1
        if ignored:
            logging.getLogger("flexflow_tpu").debug(
                "reference flags with no effect here, ignored: %s",
                ", ".join(ignored))
        return cfg


@dataclasses.dataclass
class FFIterationConfig:
    """Per-iteration config (reference ``config.h:162-167``)."""
    seq_length: int = -1

    def reset(self):
        self.seq_length = -1
