"""Inference session: bucketed, cached, eval-mode jitted forwards.

The Triton backend's per-model execution context
(``/root/reference/triton/src/model_instance_state.cc`` equivalent)
reduced to what matters on TPU: a warm XLA executable per (batch-bucket,
input-shape) and zero-copy host->device batch assembly.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import request_trace
from ..resilience import faults


def _next_bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class InferenceSession:
    """Wraps a compiled FFModel for serving.

    Requests of any batch size are padded up to the nearest bucket so
    XLA compiles once per bucket (the recompile-avoidance trick Triton
    gets from its preferred_batch_size config).
    """

    def __init__(self, ff, batch_buckets: Sequence[int] = (1, 4, 16, 64),
                 decode_segment: int = 32):
        if ff.executor is None:
            raise ValueError("compile() the model first")
        self.ff = ff
        self.buckets = sorted(set(int(b) for b in batch_buckets))
        # greedy decodes longer than this run in decode_segment-token
        # chunks, RELEASING the instance lock between chunks — a
        # 512-token generate no longer starves every short infer()
        # queued on the same instance for its whole duration. 0
        # disables segmentation (one lock hold, the legacy behavior).
        self.decode_segment = int(decode_segment)
        self._fwd = ff.executor.make_forward()
        self._lock = threading.Lock()

    def clone(self) -> "InferenceSession":
        """A concurrent instance of the same model: shares the compiled
        forward and parameters, carries its OWN dispatch lock — jitted
        executions are thread-safe, so clones genuinely overlap
        (Triton's instance_group over one device)."""
        c = InferenceSession.__new__(InferenceSession)
        c.ff = self.ff
        c.buckets = self.buckets
        c.decode_segment = self.decode_segment
        c._fwd = self._fwd
        c._lock = threading.Lock()
        return c

    @property
    def input_names(self) -> List[str]:
        return [t.name for t in self.ff.graph_inputs]

    @property
    def input_signature(self) -> Dict[str, Tuple[Tuple[int, ...],
                                                 np.dtype]]:
        """name -> (compile-time shape, numpy dtype) for each graph
        input. ``shape[0]`` is the COMPILE-TIME batch size — requests
        may send any row count; the scheduler's admission validation
        compares only ``shape[1:]`` and the dtype."""
        return {t.name: (tuple(t.shape), np.dtype(t.jnp_dtype))
                for t in self.ff.graph_inputs}

    def infer(self, inputs: Dict[str, np.ndarray]) -> np.ndarray:
        """Run one batch; pads to the bucket and slices the result.
        Batches larger than the biggest bucket run in bucket-sized
        chunks (one executable, several dispatches). Client errors
        (missing inputs, ragged rows) raise :class:`ValueError` — not
        ``assert``, which vanishes under ``python -O`` and would turn
        them into shape crashes deep in XLA."""
        if faults.active():
            faults.raise_infer_fault()
        return self._infer_checked(inputs)

    def _infer_checked(self, inputs: Dict[str, np.ndarray]) -> np.ndarray:
        # chunk recursion goes through here, NOT infer(): the fault
        # hook must advance the infer_fail@N counter exactly once per
        # top-level call or clause indices stop matching request counts
        names = self.input_names
        missing = [n for n in names if n not in inputs]
        if missing:
            raise ValueError(f"missing inputs: {missing}")
        n = int(next(iter(inputs.values())).shape[0])
        cap = self.buckets[-1]
        if n > cap:
            return np.concatenate(
                [self._infer_checked(
                    {k: v[i:i + cap] for k, v in inputs.items()})
                 for i in range(0, n, cap)], axis=0)
        bucket = _next_bucket(n, self.buckets)
        padded = {}
        for name in names:
            arr = np.ascontiguousarray(inputs[name])
            if arr.shape[0] != n:
                raise ValueError(f"ragged batch: {name} has "
                                 f"{arr.shape[0]} rows, want {n}")
            if bucket != n:
                pad = np.zeros((bucket - n,) + arr.shape[1:], arr.dtype)
                arr = np.concatenate([arr, pad], axis=0)
            padded[name] = arr
        with self._lock:  # jax dispatch of ONE model's forward at a time
            out = self._fwd(self.ff.params, self.ff.state, padded)
        return np.asarray(out)[:n]

    def generate(self, input_ids: np.ndarray,
                 prompt_len: "int | np.ndarray",
                 max_new_tokens: int, temperature: float = 0.0,
                 seed: int = 0,
                 eos_token_id: "int | None" = None,
                 top_k: int = 0, top_p: float = 1.0,
                 num_beams: int = 1) -> np.ndarray:
        """Autoregressive decode for causal-LM sessions. ``prompt_len``
        may be a per-row (batch,) array (ragged prompts). Batch is
        padded to the bucket (decode programs cache per bucket inside
        ``FFModel.generate``); the padded rows' outputs are sliced off."""
        # same chaos hook as infer(): generate IS the serving path a
        # fleet chaos plan (infer_fail@N / infer_crash@N) must reach.
        # Each bucket-sized chunk of an oversized batch advances the
        # call counter once (chunks are separate device dispatches),
        # which keeps clause indices deterministic per workload.
        if faults.active():
            faults.raise_infer_fault()
        ids = np.ascontiguousarray(np.asarray(input_ids, np.int32))
        n = int(ids.shape[0])
        ragged = np.ndim(prompt_len) > 0
        if ragged:
            if num_beams > 1:
                raise ValueError("per-row prompt lengths are not "
                                 "supported with beam search; send "
                                 "uniform-length beams or one request "
                                 "per row")
            prompt_len = np.asarray(prompt_len, np.int32)
        cap = self.buckets[-1]
        if n > cap:
            # per-chunk seed: identical prompts in different chunks must
            # not draw identical sampling streams. Wide-stride fold so a
            # separate request using seed+1 does not collide with chunk 1
            # of this request (the streams only meet after ~2^31 seeds).
            return np.concatenate(
                [self.generate(ids[i:i + cap],
                               prompt_len[i:i + cap] if ragged
                               else prompt_len,
                               max_new_tokens, temperature,
                               (seed + (i // cap) * 0x9E3779B1)
                               & 0x7FFFFFFF, eos_token_id,
                               top_k=top_k, top_p=top_p,
                               num_beams=num_beams)
                 for i in range(0, n, cap)], axis=0)
        # ambient request trace (set by the HTTP front): generate runs
        # on the caller's thread, so its lifecycle stages — batch
        # padding here, the instance-lock wait below, the prefill/
        # decode spans inside FFModel.generate — link into the request
        trace = request_trace.current()
        t_pad = time.perf_counter()
        bucket = _next_bucket(n, self.buckets)
        if bucket != n:
            pad = np.zeros((bucket - n,) + ids.shape[1:], ids.dtype)
            ids = np.concatenate([ids, pad], axis=0)
            if ragged:
                # padded rows decode from a dummy 1-token prompt
                prompt_len = np.concatenate(
                    [prompt_len, np.ones(bucket - n, np.int32)])
        if trace is not None:
            trace.stage("batch", t_pad, bucket=str(bucket), rows=n)
        seg = int(getattr(self, "decode_segment", 0) or 0)
        if (num_beams == 1 and temperature == 0.0 and not top_k
                and top_p >= 1.0 and 0 < seg < max_new_tokens):
            # greedy decode is deterministic, so it can run in bounded
            # segments with the lock RELEASED between them — short
            # infer() calls on this instance interleave instead of
            # waiting out the whole generation. Sampling paths keep the
            # single hold: the RNG stream is keyed to one scan.
            out = self._generate_segmented(ids, prompt_len,
                                           max_new_tokens, seg,
                                           eos_token_id, ragged)
            return np.asarray(out)[:n]
        t_lock = time.perf_counter()
        with self._lock:
            if trace is not None:
                # instance-lock wait = this request's queue time on the
                # single-hold decode path
                trace.stage("queue", t_lock, bucket=str(bucket))
            if num_beams > 1:
                # beam search is deterministic: temperature/top-k/top-p
                # do not apply
                out = self.ff.generate_beam(ids, prompt_len,
                                            max_new_tokens,
                                            num_beams=num_beams,
                                            eos_token_id=eos_token_id)
            else:
                out = self.ff.generate(ids, prompt_len, max_new_tokens,
                                       temperature=temperature,
                                       seed=seed,
                                       eos_token_id=eos_token_id,
                                       top_k=top_k, top_p=top_p)
        return np.asarray(out)[:n]

    def _generate_segmented(self, ids: np.ndarray,
                            prompt_len, max_new_tokens: int, seg: int,
                            eos_token_id, ragged: bool) -> np.ndarray:
        """Greedy decode in bounded lock-hold segments, bit-exact with
        the single-hold path: each segment continues from the previous
        one's ids with the prompt length advanced. Rows that emitted
        ``eos`` in an earlier segment have their later columns forced
        back to ``eos`` on the host — exactly what the in-program
        done-mask does inside one segment — so early-stopped rows read
        identically however the generation was segmented (rows are
        batch-independent under causal attention, so a finished row's
        forced columns cannot perturb its neighbors)."""
        out = np.asarray(ids)
        b, L = out.shape
        plen = (np.asarray(prompt_len, np.int32) if ragged
                else int(prompt_len))
        done = np.zeros(b, bool)
        col = np.arange(L)[None, :]
        trace = request_trace.current()
        seg_idx = 0
        offset, remaining = 0, int(max_new_tokens)
        while remaining > 0:
            step = min(seg, remaining)
            cur = plen + offset
            t_wait = time.perf_counter()
            with self._lock:
                if trace is not None and seg_idx == 0:
                    # first lock acquisition = the request's queue time
                    # on this instance (later waits show up as gaps
                    # between decode_segment spans)
                    trace.stage("queue", t_wait, bucket=str(b))
                t_step = time.perf_counter()
                # np.array (copy): the device buffer view is read-only
                # and the eos forcing below writes in place
                out = np.array(self.ff.generate(
                    out, cur, step, temperature=0.0,
                    eos_token_id=eos_token_id))
            if trace is not None:
                trace.stage("decode_segment", t_step, segment=seg_idx,
                            tokens=step, bucket=str(b))
            seg_idx += 1
            if eos_token_id is not None:
                starts = np.asarray(cur, np.int64) if ragged \
                    else np.full(b, cur, np.int64)
                seg_cols = (col >= starts[:, None]) \
                    & (col < (starts + step)[:, None])
                if done.any():
                    out[done[:, None] & seg_cols] = eos_token_id
                done |= np.where(seg_cols, out == eos_token_id,
                                 False).any(axis=1)
            offset += step
            remaining -= step
        return out


class ServingPlanSession:
    """Bucket-routed instances of a searched serving plan
    (``search/serving_plan.optimize_serving_strategy``).

    One compiled model per batch bucket, each imported from the plan's
    per-bucket sub-strategy: a batch-1 request rides the latency-lean
    (typically tensor-parallel) plan, a batch-64 request the
    throughput (data-parallel) plan — per-batch-class parallelization
    instead of one compromise strategy. Duck-typed to
    :class:`InferenceSession` (``infer``/``generate``/``clone``/
    ``input_names``/``input_signature``/``buckets``/``ff``) so
    :class:`~flexflow_tpu.serving.scheduler.BatchScheduler` and both
    HTTP fronts serve it unchanged."""

    def __init__(self, sessions: Dict[int, InferenceSession]):
        if not sessions:
            raise ValueError("need at least one bucket session")
        self._by_bucket = {int(b): s for b, s in dict(sessions).items()}
        self.buckets = sorted(self._by_bucket)
        # adoption-time measured floor-guard decisions, when the guard
        # ran (build_serving_plan_session): bucket -> {searched_s,
        # baseline_s, adopted}
        self.floor_guard: Dict = {}

    @property
    def ff(self):
        """The largest bucket's model — the one the serving envelope
        gate was enforced at (KV-cache fallback/health introspection
        reads this instance)."""
        return self._by_bucket[self.buckets[-1]].ff

    def session_for(self, n: int) -> InferenceSession:
        """The per-bucket instance a batch of ``n`` rows routes to."""
        return self._by_bucket[_next_bucket(n, self.buckets)]

    @property
    def input_names(self) -> List[str]:
        return self._by_bucket[self.buckets[-1]].input_names

    @property
    def input_signature(self):
        return self._by_bucket[self.buckets[-1]].input_signature

    def infer(self, inputs: Dict[str, np.ndarray]) -> np.ndarray:
        n = int(next(iter(inputs.values())).shape[0])
        # oversized batches ride the largest bucket's own chunking
        return self.session_for(n).infer(inputs)

    def generate(self, input_ids: np.ndarray,
                 prompt_len: "int | np.ndarray",
                 max_new_tokens: int, temperature: float = 0.0,
                 seed: int = 0, eos_token_id: "int | None" = None,
                 top_k: int = 0, top_p: float = 1.0,
                 num_beams: int = 1) -> np.ndarray:
        n = int(np.asarray(input_ids).shape[0])
        return self.session_for(n).generate(
            input_ids, prompt_len, max_new_tokens,
            temperature=temperature, seed=seed,
            eos_token_id=eos_token_id, top_k=top_k, top_p=top_p,
            num_beams=num_beams)

    def clone(self) -> "ServingPlanSession":
        c = ServingPlanSession(
            {b: s.clone() for b, s in self._by_bucket.items()})
        c.floor_guard = self.floor_guard
        return c

    def measured_profile(self) -> Dict[str, Dict]:
        """Measured per-bucket decode reality, keyed 1:1 to the serving
        audit block's ``predicted`` entries: bucket label ->
        ``{prefill_s, decode_step_s, n}`` — the min-tracked sink
        ``FFModel._generate_kv`` maintains per batch size on each
        bucket's model.  Buckets that have served no generate traffic
        yet are absent (``obs.drift.serving_drift_report`` skips them
        rather than report drift on zero measurements).  Clones share
        the underlying ``ff``, so any instance's traffic lands here."""
        out: Dict[str, Dict] = {}
        for b, s in self._by_bucket.items():
            rec = getattr(s.ff, "_decode_measured", {}).get(int(b))
            if rec:
                out[str(b)] = dict(rec)
        return out


def _min_decode_latency(ff, bucket: int, hist, reps: int = 3) -> float:
    """Min measured per-token decode-step latency of ``ff`` at
    ``bucket`` rows (read from the ``ff_decode_step_seconds`` histogram
    the KV-decode path observes — decode phase only, prefill excluded).
    The first call warms/compiles and is not timed. Raises when the
    graph has no generate path (non-causal-LM) — callers treat that as
    'guard not applicable'."""
    t = next(t for t in ff.graph_inputs if t.name == "input_ids")
    seq = int(t.shape[1])
    plen = max(1, seq // 4)
    new_tokens = max(1, min(8, seq - plen))
    ids = np.zeros((bucket, seq), np.int32)
    np.asarray(ff.generate(ids, plen, new_tokens, temperature=0.0))
    best = float("inf")
    for _ in range(reps):
        before = hist.sum(bucket=str(bucket))
        np.asarray(ff.generate(ids, plen, new_tokens, temperature=0.0))
        best = min(best, hist.sum(bucket=str(bucket)) - before)
    return best


def build_serving_plan_session(serving_strategy_file: str, build,
                               floor_guard: str = "auto"
                               ) -> ServingPlanSession:
    """One compiled model per bucket of a serving-plan artifact: each
    bucket's sub-strategy is extracted into a standalone single-bucket
    strategy doc (``serving_plan.bucket_strategy_doc`` — so compile's
    plan verifier gates the KV envelope AT that bucket) and imported
    through the ordinary strategy-file path. ``build(sf, buckets=...)``
    compiles one session from a strategy file (``sf=None`` = the model
    as it would load WITHOUT a serving plan — the reused-training-plan
    baseline the floor guard compares against).

    ``floor_guard`` (``FFConfig.serving_floor_guard``): the measured
    decode floor on adoption. Like the training search's
    ``_apply_floor_guard``, the protection is direct measurement, not
    trust in the cost model: per bucket, a few greedy decodes of the
    imported plan AND the baseline run back to back, and the bucket
    keeps whichever measures faster (records in
    ``ServingPlanSession.floor_guard``). "auto" skips on bare-CPU
    backends (the extra baseline compile is expensive on the CPU sim);
    any failure to measure keeps the searched plan — the guard must
    never kill a load."""
    import json
    import os
    import tempfile
    import time

    from ..search.serving_plan import bucket_strategy_doc
    with open(serving_strategy_file) as f:
        doc = json.load(f)
    sblock = doc.get("serving") or {}
    bks = sorted(int(k) for k in (sblock.get("buckets") or {}))
    if not bks:
        raise ValueError(
            f"{serving_strategy_file} has no serving block — "
            f"search one with optimize_serving_strategy "
            f"(mode='serving') or pass it as strategy_file")
    per_bucket = {}
    for b in bks:
        sub = bucket_strategy_doc(doc, b)
        fd, p = tempfile.mkstemp(suffix=f".bucket{b}.json")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(sub, f)
            per_bucket[b] = build(p, buckets=[b])
        finally:
            try:
                os.unlink(p)
            except OSError:
                pass

    mode = str(floor_guard or "auto").lower()
    guard = mode not in ("false", "off", "0", "no")
    if guard and mode == "auto":
        import jax
        guard = jax.devices()[0].platform != "cpu"
    records = {}
    if guard:
        from ..obs import events as obs_events
        from ..obs.metrics_registry import DECODE_STEP_BUCKETS, REGISTRY
        hist = REGISTRY.histogram(
            "ff_decode_step_seconds",
            "Per-token decode-step latency by batch bucket",
            buckets=DECODE_STEP_BUCKETS)
        t0 = time.perf_counter()
        try:
            base = build(None, buckets=list(bks))
            for b in bks:
                t_s = _min_decode_latency(per_bucket[b].ff, b, hist)
                t_b = _min_decode_latency(base.ff, b, hist)
                adopted = "searched" if t_s <= t_b else "baseline"
                if adopted == "baseline":
                    per_bucket[b] = InferenceSession(
                        base.ff, [b],
                        decode_segment=per_bucket[b].decode_segment)
                records[b] = {"searched_s": t_s, "baseline_s": t_b,
                              "adopted": adopted}
        except Exception as e:  # noqa: BLE001 — guard never kills a load
            records = {"skipped": f"{type(e).__name__}: {e}"[:500]}
            import logging
            logging.getLogger("flexflow_tpu").warning(
                "serving floor guard skipped: %s", records["skipped"])
        obs_events.record_span(
            "serving.floor_guard", t0, time.perf_counter() - t0,
            buckets=len(bks))
    session = ServingPlanSession(per_bucket)
    session.floor_guard = records
    return session


class ModelRepository:
    """Name -> session-instances registry (Triton model repository +
    instance groups, ``triton/src/backend.cc``/``instance.cc``).

    Each model may have N concurrent instances (session replicas); the
    HTTP layer gives all of them to one :class:`BatchScheduler`, whose
    per-instance workers drain a shared bounded queue. Models can be
    loaded/unloaded by name at runtime (Triton repository API)."""

    def __init__(self):
        self._models: Dict[str, List[InferenceSession]] = {}

    def register(self, name: str, session: InferenceSession,
                 instances: "int | None" = None):
        """Register a model. Pass a list of sessions OR ``instances=N``
        to clone one session N times — clones share the compiled
        forward and weights but have independent dispatch locks, so
        the N scheduler workers genuinely overlap (Triton instances
        sharing one device)."""
        if isinstance(session, (list, tuple)):
            self._models[name] = list(session)
        elif instances and instances > 1:
            self._models[name] = [session] + [
                session.clone() for _ in range(int(instances) - 1)]
        else:
            self._models[name] = [session]

    def unload(self, name: str):
        """Remove a model by name (Triton ``.../unload``)."""
        if name not in self._models:
            raise KeyError(f"model {name!r} not loaded")
        del self._models[name]

    def load_graph(self, name: str, path: str,
                   input_shapes: Sequence[Sequence[int]],
                   checkpoint_dir: Optional[str] = None,
                   batch_buckets: Sequence[int] = (1, 4, 16, 64),
                   config=None, strategy_file=None, instances: int = 1,
                   serving_strategy_file=None):
        """Serve a serialized graph (``PyTorchModel.torch_to_file`` /
        strategy-export output) without its source framework: rebuild
        through ``file_to_ff``, optionally restore trained weights from
        a checkpoint, and register an eval session.

        ``strategy_file`` imports a searched strategy instead of plain
        data parallelism; pass a LIST (one entry per instance, None =
        DP) to give each instance its own parallelization — the
        reference Triton backend's per-instance strategy files
        (``triton/src/instance.cc``). A single value with
        ``instances=N`` compiles once and clones (instances sharing one
        program); a list compiles each instance separately."""
        from ..frontends.torch_fx import PyTorchModel

        def graph_build(ff):
            ins = [ff.create_tensor(tuple(s), name=f"in{i}")
                   for i, s in enumerate(input_shapes)]
            outs = PyTorchModel.file_to_ff(path, ff, ins)
            return outs[0]

        return self._load_with_builder(
            name, graph_build, batch_buckets=batch_buckets, config=config,
            strategy_file=strategy_file, instances=instances,
            checkpoint_dir=checkpoint_dir,
            serving_strategy_file=serving_strategy_file)

    def load_onnx(self, name: str, path_or_model,
                  input_shapes: Optional[Dict[str, Sequence[int]]] = None,
                  checkpoint_dir: Optional[str] = None,
                  batch_buckets: Sequence[int] = (1, 4, 16, 64),
                  config=None, strategy_file=None, instances: int = 1,
                  serving_strategy_file=None):
        """Serve an ONNX model torch-free (the reference Triton
        backend's direct ONNX ingestion, ``triton/src/onnx_parser.cc``):
        rebuild the graph through ``frontends.onnx_frontend.ONNXModel``,
        transfer the initializer weights after compile, and register
        sessions. ``input_shapes`` overrides/maps graph-input name ->
        shape (required for inputs with symbolic batch dims);
        ``strategy_file``/``instances`` behave as in
        :meth:`load_graph`."""
        from ..frontends.onnx_frontend import ONNXModel
        model = ONNXModel(path_or_model)
        graph = model.model.graph
        fed = [vi for vi in graph.input
               if vi.name not in model.initializers]
        # elem_type -> framework dtype (TensorProto enum values)
        from ..ffconst import DataType
        dt_map = {1: DataType.DT_FLOAT, 6: DataType.DT_INT32,
                  7: DataType.DT_INT64, 9: DataType.DT_BOOLEAN,
                  10: DataType.DT_HALF, 16: DataType.DT_BFLOAT16}

        def shape_of(vi):
            if input_shapes and vi.name in input_shapes:
                return tuple(int(d) for d in input_shapes[vi.name])
            dims = []
            for d in vi.type.tensor_type.shape.dim:
                if d.dim_param or d.dim_value <= 0:
                    raise ValueError(
                        f"ONNX input {vi.name!r} has a symbolic dim "
                        f"{d.dim_param or '?'} — pass input_shapes")
                dims.append(int(d.dim_value))
            return tuple(dims)

        def onnx_build(ff):
            ins = {vi.name: ff.create_tensor(
                shape_of(vi), name=vi.name,
                dtype=dt_map.get(vi.type.tensor_type.elem_type,
                                 DataType.DT_FLOAT)) for vi in fed}
            outs = model.apply(ff, ins)
            return outs[0]

        return self._load_with_builder(
            name, onnx_build, batch_buckets=batch_buckets, config=config,
            strategy_file=strategy_file, instances=instances,
            checkpoint_dir=checkpoint_dir,
            post_compile=model.copy_weights,
            serving_strategy_file=serving_strategy_file)

    def _load_with_builder(self, name, graph_build, batch_buckets,
                           config, strategy_file, instances,
                           checkpoint_dir=None, post_compile=None,
                           serving_strategy_file=None):
        """Shared per-instance loading: one compiled session per
        strategy-file entry (None = plain DP), or one session cloned
        ``instances`` times (replicas sharing the compiled program) —
        the reference Triton backend's per-instance strategy files
        (``triton/src/instance.cc``).

        ``serving_strategy_file`` adopts a searched per-batch-class
        serving plan (a strategy export whose ``serving`` block carries
        one sub-strategy per bucket): one model is compiled per bucket
        and requests route by batch size through a
        :class:`ServingPlanSession`. Mutually exclusive with
        ``strategy_file``."""
        import copy

        from ..config import FFConfig
        from ..model import FFModel
        from ..runtime.optimizers import SGDOptimizer
        from ..utils.compilation_cache import enable_compilation_cache

        if serving_strategy_file and strategy_file:
            raise ValueError("pass strategy_file OR "
                             "serving_strategy_file, not both")
        per_instance = isinstance(strategy_file, (list, tuple))
        files = (list(strategy_file) if per_instance
                 else [strategy_file])
        if per_instance and instances != 1 and instances != len(files):
            raise ValueError(
                f"instances={instances} conflicts with "
                f"{len(files)} per-instance strategy files — the list "
                f"length alone sets the instance count")

        def build(sf, buckets=batch_buckets):
            cfg = copy.deepcopy(config) if config is not None \
                else FFConfig()
            if sf:
                cfg.import_strategy_file = sf
                cfg.only_data_parallel = False
            else:
                cfg.only_data_parallel = True
                # a None list entry means plain DP for THIS instance:
                # clear any import the caller's config carried, or the
                # instance would silently adopt that strategy instead
                cfg.import_strategy_file = ""
            # warm start: every repository load compiles through the
            # persistent cache (placed by utils/compilation_cache.py),
            # so a fresh serving process re-loading the same model hits
            # disk instead of re-paying XLA. Recompiles stay visible
            # through ff_model_compiles_total{model=...}.
            enable_compilation_cache()
            ff = FFModel(cfg)
            ff._model_name = name   # labels compile/fallback counters
            out = graph_build(ff)
            ff.compile(SGDOptimizer(0.0), "identity", [],
                       output_tensor=out)
            if post_compile is not None:
                post_compile(ff)
            if checkpoint_dir:
                from ..runtime.checkpoint import restore_model_checkpoint
                restore_model_checkpoint(ff, checkpoint_dir)
            return InferenceSession(ff, buckets)

        if serving_strategy_file:
            session = build_serving_plan_session(
                serving_strategy_file, build,
                floor_guard=getattr(config, "serving_floor_guard",
                                    "auto") if config is not None
                else "auto")
            self.register(name, session, instances=instances)
            return session

        sessions = [build(sf) for sf in files]
        if per_instance:
            self.register(name, sessions)
        else:
            # register's own clone path handles instances=N
            self.register(name, sessions[0], instances=instances)
        return sessions[0]

    # backward-compat alias: the per-bucket build + measured floor
    # guard live in the module-level build_serving_plan_session
    _build_serving_plan = staticmethod(build_serving_plan_session)

    def hot_swap(self, name: str, session, instances: "int | None" = None,
                 scheduler=None, deadline_s: float = 10.0):
        """Replace a loaded model's instances in place — the adoption
        point for a re-searched serving plan. With ``scheduler`` (the
        model's :class:`~flexflow_tpu.serving.scheduler.BatchScheduler`)
        the swap rides the graceful-drain path: admission pauses
        (503 + ``Retry-After``), the admitted backlog flushes on the
        OLD instances, then workers restart on the new ones — no
        admitted request is dropped. Without a scheduler it is a bare
        registry swap (single-session deployments)."""
        if name not in self._models:
            raise KeyError(f"model {name!r} not loaded "
                           f"(have {list(self._models)})")
        self.register(name, session, instances=instances)
        if scheduler is not None:
            scheduler.hot_swap(self.get_instances(name),
                               deadline_s=deadline_s)
        return self.get(name)

    def get(self, name: str) -> InferenceSession:
        """First (primary) instance — the single-session API."""
        return self.get_instances(name)[0]

    def get_instances(self, name: str) -> List[InferenceSession]:
        if name not in self._models:
            raise KeyError(
                f"model {name!r} not loaded (have {list(self._models)})")
        return self._models[name]

    def names(self) -> List[str]:
        return list(self._models)
