"""Compile sweep of the two flash backward kernels for a described v5e
(no chip: ``libtpu`` describes the topology, Mosaic compiles for it).

What ``flash_attention.bwd_tiles`` may hand a kernel is every tile whose
working set, as ``_bwd_vmem_bytes`` counts it, fits ``BWD_VMEM_BUDGET``.
The sweep compiles both kernels over operand types (bf16, f32), head
sizes (64, 128, 256 and q.k 192 / p.v 128), dropout, causal and the ten
tiles of 256 to 2,048 a side (the resident side the larger: q for
``bwd_dq``, k for ``bwd_dkv``): 480 compiles at equal head sizes, 160
more at 192 / 128. It passes where every tile the rule admits compiles;
a tile Mosaic refuses (over its 16 MiB of scoped VMEM) has to count over
the budget. Run it after a change to either kernel's body, operands or
scratch, before any chip time (PERF.md section 6, PR 28 and PR 39).
``--kv-group N`` hands k and v at an N-th of q's heads (grouped-query
attention read in place, PR 52: ``bwd_dkv``'s other grid and index
maps); the default, 1, is the kernels at equal head counts.

    JAX_PLATFORMS=cpu python3 examples/flash_backward_compile_sweep.py \\
        [--kv-group 4] [--out chiprun_out/bwd_sweep.jsonl]
"""
import argparse
import importlib
import itertools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

SIDES = (256, 512, 1024, 2048)
HEADS = ((64, 64), (128, 128), (256, 256), (192, 128))
S, BH = 2048, 2


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="one JSON line a compile")
    ap.add_argument("--kv-group", type=int, default=1,
                    help="query heads that read one key/value head")
    args = ap.parse_args()
    heads = BH * args.kv_group

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    out = open(args.out, "w") if args.out else None
    rows, t_start = [], time.time()
    for (kernel, call), dtype, (d, dv), dropout, causal, (small, large) in \
            itertools.product(
                (("bwd_dq", fa._bwd_dq_call), ("bwd_dkv", fa._bwd_dkv_call)),
                ("bfloat16", "float32"), HEADS, (False, True), (False, True),
                itertools.combinations_with_replacement(SIDES, 2)):
        dtype = jnp.dtype(dtype)
        bq, bk = (large, small) if kernel == "bwd_dq" else (small, large)
        counted = fa._bwd_vmem_bytes(kernel, bq, bk, d, dtype.itemsize,
                                     dropout, dv)
        operands = (shape((1, 1), jnp.int32), shape((heads, S, d), dtype),
                    shape((BH, S, d), dtype), shape((BH, S, dv), dtype),
                    shape((heads, S, dv), dtype),
                    shape((heads, 1, S), jnp.float32),
                    shape((heads, 1, S), jnp.float32))
        try:
            jax.jit(lambda *a: call(
                *a, S, d ** -0.5, causal, bq, bk, 0.1 if dropout else 0.0,
                False)).lower(*operands).compile()
            refused = None
        except Exception as e:  # noqa: BLE001 — whatever Mosaic raises
            refused = str(e).splitlines()[0][:200]
        row = {"kernel": "flash_attention_" + kernel,
               "tile_form": fa.grid_steps(kernel, heads, S, S, bq, bk,
                                          causal)["tile"],
               "kv_group": args.kv_group,
               "dtype": dtype.name, "d": d, "dv": dv, "dropout": dropout,
               "causal": causal, "block_q": bq, "block_k": bk,
               "counted_mib": counted / 2 ** 20,
               "admitted": counted <= fa.BWD_VMEM_BUDGET
               and max(bq, bk) <= fa.MAX_BWD_TILE,
               "fits_budget": counted <= fa.BWD_VMEM_BUDGET,
               "compiled": refused is None, "refused": refused}
        rows.append(row)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()
    bad = [r for r in rows if r["fits_budget"] and not r["compiled"]]
    refused = [r for r in rows if not r["compiled"]]
    equal = [r for r in rows if r["d"] == r["dv"]]
    print(f"{len(rows)} compiles ({len(equal)} at equal head sizes, "
          f"{len(rows) - len(equal)} at 192 / 128) in "
          f"{time.time() - t_start:.0f} s: {len(rows) - len(refused)} "
          f"compiled, {len(refused)} refused; "
          f"{sum(r['admitted'] for r in rows)} are tiles the rule may hand "
          f"out, {sum(r['fits_budget'] for r in rows)} count within the "
          f"budget")
    if refused:
        print("least count Mosaic refused: "
              f"{min(r['counted_mib'] for r in refused):.2f} MiB; most it "
              "compiled: "
              f"{max(r['counted_mib'] for r in rows if r['compiled']):.2f}")
    for r in bad:
        print("REFUSED WITHIN THE BUDGET:", json.dumps(r))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
