"""Parallelization strategy: per-op sharding assignment.

The searched artifact. Reference analog: the (PCG, MachineView map) pair
produced by ``Graph::graph_optimize_task`` — here it is a map
layer-name → {output PartitionSpecs, weight PartitionSpecs} over one global
device mesh. The executor turns these into ``NamedSharding`` constraints
inside the jitted step; XLA GSPMD then inserts the ICI collectives the
reference expressed as explicit parallel ops + NCCL cliques.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from jax.sharding import NamedSharding, PartitionSpec as P

from ..ffconst import OperatorType, PARALLEL_OPS
from .machine import DeviceMesh


def _spec_axes(spec) -> List[str]:
    axes: List[str] = []
    for e in spec:
        if e is None:
            continue
        if isinstance(e, (tuple, list)):
            axes.extend(e)
        else:
            axes.append(e)
    return axes


@dataclasses.dataclass
class OpSharding:
    """Sharding of one op's outputs and weights."""
    outputs: List[Optional[P]] = dataclasses.field(default_factory=list)
    weights: Dict[str, P] = dataclasses.field(default_factory=dict)

    def degree_of(self, dmesh: DeviceMesh, out_idx: int = 0) -> int:
        spec = self.outputs[out_idx]
        if spec is None:
            return 1
        d = 1
        for a in _spec_axes(spec):
            d *= dmesh.axis_sizes[a]
        return d


class ShardingStrategy:
    """Complete strategy for a graph over a mesh."""

    def __init__(self, dmesh: DeviceMesh):
        self.dmesh = dmesh
        self.ops: Dict[str, OpSharding] = {}
        self.inputs: Dict[str, P] = {}   # input tensor name -> spec
        # set by parallel.presets.pipeline_strategy: a PipelineRegion the
        # executor lowers onto the GPipe engine (None = no pipelining)
        self.pipeline = None
        # per-op concurrent device-subset placements (parallel/banks.py
        # BankSpec list) — the reference's MachineView concept
        # (machine_view.h:14-62); member ops run on disjoint subsets
        self.banks: List = []
        # heterogeneous-op placement regions (parallel/banks.py
        # PlaceGroup list): mixed op types on disjoint axis blocks,
        # lowered as a lax.switch shard_map region (MPMD-inside-SPMD)
        self.place_groups: List = []
        # hierarchical placement annotations (parallel/placement.py,
        # arXiv 2110.10548), set by a placement-aware search:
        #   axis_tiers       — mesh axis -> hardware tier ("ici"/"host"/
        #                      "dcn") the adopted placement assigned;
        #   collective_trees — per-collective-site chosen reduction-tree
        #                      records ({site, collective, degree,
        #                      tier_path, algo, phases, cost_s, ...})
        # Both serialize with the strategy and are statically checked by
        # analysis/plan_verifier's placement pass.
        self.axis_tiers: Dict[str, str] = {}
        self.collective_trees: List[Dict] = []
        # per-parameter optimizer-state sharding (runtime/zero.py
        # ZeroAssignment, planned by search/zero_plan.py per arXiv
        # 2004.13336): layer -> weight -> {spec, degree, bytes_saved,
        # overhead_s}. None = fully replicated optimizer state (or the
        # legacy uniform --zero flag, which bypasses the assignment).
        # Serializes with the strategy and is statically checked by
        # analysis/plan_verifier's zero pass.
        self.zero = None
        # quantized gradient collectives (ops/quantized_collectives.py
        # QsyncPlan, arXiv 2506.17615): per-tensor, per-phase wire
        # dtype of each gradient sync — quantize the slow (DCN) legs,
        # keep ICI legs and every replicated-math seam full-precision.
        # None = every sync at the element dtype. Serializes with the
        # strategy (--import honors it verbatim) and is statically
        # checked by analysis/plan_verifier's qsync pass.
        self.qsync = None
        # per-(model, batch-class) serving plans (search/serving_plan.py
        # ServingPlan.to_block() JSON): one sub-strategy per batch
        # bucket + the KV-cache geometry/shard degrees. None for
        # training strategies. Serializes as the artifact's "serving"
        # block and is statically checked by analysis/plan_verifier's
        # serving pass (KV sharding sound, envelope fits at the largest
        # bucket).
        self.serving = None
        # forced kernel-implementation assignment (kernels/registry.py,
        # adopted by FFModel._plan_kernels): the "attention" kind key
        # and layer-name -> impl for attention ops ("attn0": "ring").
        # {} / missing key = the op's own rule. Serializes as the
        # artifact's "kernel_impls" block (--import honors it verbatim)
        # and is statically checked by analysis/plan_verifier's kernel
        # pass (every chosen impl's availability predicate must hold on
        # the adopted mesh/shapes).
        self.kernel_impls: Dict[str, str] = {}

    # ------------------------------------------------------------------
    def set_op(self, layer_name: str, outputs: Sequence[Optional[P]],
               weights: Optional[Dict[str, P]] = None):
        self.ops[layer_name] = OpSharding(list(outputs), dict(weights or {}))

    def output_sharding(self, layer_name: str, idx: int = 0
                        ) -> Optional[NamedSharding]:
        os = self.ops.get(layer_name)
        if os is None or idx >= len(os.outputs) or os.outputs[idx] is None:
            return None
        return NamedSharding(self.dmesh.mesh, os.outputs[idx])

    def tensor_spec(self, tensor) -> Optional[P]:
        """The adopted spec of a tensor of the graph (its producer's
        output, a graph input's own); None where the plan leaves it
        open."""
        if tensor.owner_layer is None:
            return self.inputs.get(tensor.name)
        os = self.ops.get(tensor.owner_layer.name)
        if os is None or tensor.owner_idx >= len(os.outputs):
            return None
        return os.outputs[tensor.owner_idx]

    def weight_sharding(self, layer_name: str, wname: str) -> NamedSharding:
        os = self.ops.get(layer_name)
        spec = os.weights.get(wname, P()) if os else P()
        return NamedSharding(self.dmesh.mesh, spec)

    def input_sharding(self, tensor_name: str) -> NamedSharding:
        return NamedSharding(self.dmesh.mesh,
                             self.inputs.get(tensor_name, P()))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.dmesh.mesh, P())

    def batch_shardings(self, graph_inputs, output_tensor
                        ) -> Dict[str, NamedSharding]:
        """Where one fed batch lives: every graph input on its planned
        sharding, and ``"label"`` on the batch axes of the final op's
        output — or replicated over the whole mesh when the plan leaves
        that op unsharded (never on device 0 alone, for the step to
        broadcast each time). The dataloader and the floor guard both
        place batches with this, so a step compiled by one is the step
        the other runs."""
        out = {t.name: self.input_sharding(t.name) for t in graph_inputs}
        out["label"] = self.replicated()
        owner = output_tensor.owner_layer
        if owner is not None and self.output_sharding(
                owner.name, output_tensor.owner_idx) is not None:
            ospec = self.ops[owner.name].outputs[output_tensor.owner_idx]
            out["label"] = NamedSharding(
                self.dmesh.mesh, P(ospec[0] if len(ospec) > 0 else None))
        return out

    # ------------------------------------------------------------------
    @classmethod
    def data_parallel(cls, layers, input_tensors, dmesh: DeviceMesh
                      ) -> "ShardingStrategy":
        """Canonical pure-DP strategy: batch dim sharded over ALL mesh axes,
        weights replicated. Analog of the reference's
        ``--only-data-parallel`` canonical view (``graph.cc:1939-1964``)."""
        st = cls(dmesh)
        # the reserved seq axis (ring attention's context axis) never
        # carries the batch dim — DP spans the general sharding axes
        axes = dmesh.sharding_axes
        nd = dmesh.sharding_devices
        batch_axes = axes if len(axes) > 1 else (axes[0] if axes else None)
        if nd == 1:
            return st  # single device: everything unsharded
        for t in input_tensors:
            if t.shape and t.shape[0] % nd == 0:
                st.inputs[t.name] = P(batch_axes)
        for layer in layers:
            outs = []
            for o in layer.outputs:
                if o.shape and o.shape[0] % nd == 0:
                    outs.append(P(batch_axes))
                else:
                    outs.append(None)
            st.set_op(layer.name, outs, {})
        return st

    # ------------------------------------------------------------------
    def validate(self) -> List[str]:
        """Check axis-use consistency within each spec (an axis may appear
        at most once per PartitionSpec)."""
        errors = []
        for name, os in self.ops.items():
            for spec in list(os.outputs) + list(os.weights.values()):
                if spec is None:
                    continue
                axes = _spec_axes(spec)
                if len(axes) != len(set(axes)):
                    errors.append(f"{name}: axis reused in {spec}")
                for a in axes:
                    if a not in self.dmesh.axis_sizes:
                        errors.append(f"{name}: unknown axis {a}")
        return errors

    def describe(self) -> str:
        lines = [f"mesh axes: {dict(self.dmesh.axis_sizes)}"]
        if self.axis_tiers:
            lines.append(f"axis tiers: {dict(self.axis_tiers)}")
        for ct in self.collective_trees:
            lines.append(
                f"  tree {ct.get('site')}/{ct.get('collective')}"
                f" x{ct.get('degree')}: {ct.get('algo')} over "
                f"{ct.get('tier_path')}")
        if self.zero is not None:
            s = self.zero.summary()
            lines.append(
                f"zero: {s['n_sharded']}/{s['n_params']} opt states "
                f"sharded ({s['policy']}), "
                f"{s['bytes_saved_total'] / 2**20:.1f} MiB/device saved")
        if self.qsync is not None:
            s = self.qsync.summary()
            lines.append(
                f"qsync: {s['n_quantized']}/{s['n_params']} grad syncs "
                f"quantized ({s['mode']}, wire {s['wire']})")
        if self.kernel_impls:
            lines.append(f"kernel impls: {dict(self.kernel_impls)}")
        for name, os in self.ops.items():
            lines.append(f"  {name}: out={os.outputs} w={os.weights}")
        for bk in self.banks:
            views = bk.machine_views(self.dmesh)
            lines.append(f"  bank over axes {bk.axes}:")
            for m in bk.members:
                lines.append(f"    {m}: devices {views[m].device_ids}")
        return "\n".join(lines)
