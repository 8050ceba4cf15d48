"""On-chip validation of the Pallas kernels (run on a real TPU).

The CI tier runs the kernels in Pallas interpret mode on CPU
(`tests/test_kernels.py`); this script is the compiled-on-TPU
counterpart: Mosaic lowering, MXU-precision numerics, and the
counter-based in-kernel dropout running compiled. (An earlier run of
this script caught two TPU-only bugs CPU CI cannot see: Mosaic's
two-word PRNG seed limit, and a per-tile-seeded mask the
differently-blocked backward could not regenerate.)

Checks (each prints PASS/FAIL, exit code 1 on any failure):
  1. fwd numerics vs the plain-XLA golden, f32 + bf16, causal on/off,
     unpadded (512) and padded (393) sequence lengths;
  2. full vjp (dq/dk/dv) vs jax.grad of the golden, the kernels at the
     tiles derived from the shapes (printed), once at the benchmark's
     cell-2 shapes (bh 144, s 1024, d 64, bf16, causal) against the
     golden at ``highest`` precision, and the forward at cell 3's (bh 32,
     s 4096, q.k over 192, p.v over 128, bf16, causal) against the same;
  3. dropout>0: deterministic under one seed, decorrelated across seeds,
     empirical keep-rate ≈ 1-rate, and vjp matches jax.grad of an
     explicit-masked golden built from the kernel's own keep-mask;
  4. the benchmark's cell-1 layer (8 x 16 x 512 x 64, bf16, dropout 0.1):
     kernels and vjp against that golden at ``highest``, the mask's keep
     share over the layer's 33.5M positions, and the attention op with
     nothing forced: ``auto`` takes the kernels there (PERF.md, PR 30);
  5. float32 operands with dropout over 2,048 and 4,096 keys, not causal
     (head sizes 128 and 256, sq != sk among them): forward and vjp
     against the explicit-mask golden at ``highest``.
"""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from flexflow_tpu.kernels import flash_attention, mha_reference  # noqa: E402
from flexflow_tpu.obs import events  # noqa: E402

FAILED = []


def check(name, ok, detail=""):
    print(f"{'PASS' if ok else 'FAIL'} {name} {detail}", flush=True)
    if not ok:
        FAILED.append(name)


def tiles():
    """The tiles of the kernels emitted since the last call of this
    (``flash.grid``): the forward's block and the pieces it walks its
    keys in, the backward kernels' where a backward was traced."""
    last = {e["attrs"]["kernel"]: e["attrs"] for e in events.events()
            if e["name"] == "flash.grid"}
    events.clear()
    fwd = last["flash_attention_fwd"]
    return " ".join(
        [f"fwd {fwd['block_q']}x{fwd['block_k']}/{fwd['piece_k']}"]
        + [f"{name} {last[kernel]['block_q']}x{last[kernel]['block_k']}"
           for name, kernel in (("dq", "flash_attention_bwd_dq"),
                                ("dkv", "flash_attention_bwd_dkv"))
           if kernel in last])


def rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-9))


def cell1_layer(rng, b=8, s=512, d=64):
    """-- 4: the benchmark's cell 1 (8 x 16 heads of 512 x 64, bf16, not
    causal, dropout 0.1), which ``auto`` puts on the kernels since PR 30:
    the kernels against the explicit-mask golden at ``highest``, the
    share of the layer's 33.5M positions the mask keeps, and the
    attention op with nothing forced, which must take the kernels."""
    import math

    from flexflow_tpu.kernels import dropout_keep_mask
    h, rate, seed = 1024 // d, 0.1, 1234567
    hi = jax.lax.Precision.HIGHEST
    q, k, v, probe = (jnp.asarray(rng.normal(size=(b, h, s, d)),
                                  jnp.bfloat16) for _ in range(4))
    keep = dropout_keep_mask(b, h, s, s, rate, seed)
    share = float(jnp.mean(keep.astype(jnp.float32)))
    check("cell1 keep share", abs(share - (1 - rate)) < 1e-3,
          f"{share:.6f} of {keep.size} positions")

    def golden(qv, kv, vv):
        qv, kv, vv = (a.astype(jnp.float32) for a in (qv, kv, vv))
        sc = jnp.einsum("bhqd,bhkd->bhqk", qv, kv,
                        precision=hi) / math.sqrt(d)
        p = jnp.where(keep, jax.nn.softmax(sc, axis=-1) / (1.0 - rate), 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vv, precision=hi)

    def kernel(qv, kv, vv):
        return flash_attention(qv, kv, vv, dropout_rate=rate,
                               dropout_seed=seed).astype(jnp.float32)

    def loss(f):
        return lambda *x: jnp.sum(f(*x) * probe.astype(jnp.float32))

    rel = rel_err(kernel(q, k, v), golden(q, k, v))
    check("cell1 dropout fwd vs explicit-mask golden at HIGHEST",
          rel < 2e-2, f"rel={rel:.2e}")
    g = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(golden), argnums=(0, 1, 2))(q, k, v)
    rels = [rel_err(a, b_) for a, b_ in zip(g, g_ref)]
    check("cell1 dropout vjp vs explicit-mask golden at HIGHEST",
          max(rels) < 4e-2,
          "rel dq={:.2e} dk={:.2e} dv={:.2e} tiles ".format(*rels)
          + tiles())

    # the op, nothing forced (the layer of ``tpu_attention_choice.py``, 16
    # heads of 64 over 1024): training takes the kernels, one step index
    # draws one mask and another another, and the gradients are finite
    import tpu_attention_choice as choice
    step, resolved = choice.make_step("auto", b, s, d, rate, False)
    args = choice.operands(b, s, d)
    (l0, g0), (l0_again, _), (l1, _) = step(*args, 0), step(*args, 0), \
        step(*args, 1)
    check("cell1 op: auto takes the kernels in training",
          resolved[choice.NAME] == "flash", f"resolved {resolved}")
    check("cell1 op: one step index, one mask; another, another",
          float(l0) == float(l0_again) != float(l1),
          f"losses {float(l0):.6f} {float(l0_again):.6f} {float(l1):.6f}")
    check("cell1 op: finite gradients", all(
        bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(g0)))


def long_f32_dropout():
    """-- 5: float32 operands with dropout over 2,048 keys and more, not
    causal: the forward walks its k block in several pieces there, and
    Mosaic refused it for a described v5e while the pieces were unrolled
    inline (PR 32's review). Forward and gradients against the
    explicit-mask golden at ``highest``, f32 tolerances as in 1/2 and 3."""
    import math

    from flexflow_tpu.kernels import dropout_keep_mask
    events.clear()                     # tiles(): this section's calls
    rng = np.random.default_rng(5)     # draws of its own
    hi = jax.lax.Precision.HIGHEST
    b, h, rate, seed = 1, 4, 0.1, 77
    for sq, sk, d in ((2048, 2048, 128), (1024, 4096, 128),
                      (2048, 2048, 256)):
        q, probe = (jnp.asarray(rng.normal(size=(b, h, sq, d)), jnp.float32)
                    for _ in range(2))
        k, v = (jnp.asarray(rng.normal(size=(b, h, sk, d)), jnp.float32)
                for _ in range(2))
        keep = dropout_keep_mask(b, h, sq, sk, rate, seed)

        def golden(qv, kv, vv):
            sc = jnp.einsum("bhqd,bhkd->bhqk", qv, kv,
                            precision=hi) / math.sqrt(d)
            p = jnp.where(keep, jax.nn.softmax(sc, axis=-1) / (1.0 - rate),
                          0.0)
            return jnp.einsum("bhqk,bhkd->bhqd", p, vv, precision=hi)

        def kernel(qv, kv, vv):
            return flash_attention(qv, kv, vv, dropout_rate=rate,
                                   dropout_seed=seed)

        tag = f"float32/dropout/sq={sq}/sk={sk}/d={d}"
        rel = rel_err(kernel(q, k, v), golden(q, k, v))
        check(f"fwd {tag}", rel < 1e-2, f"rel={rel:.2e} tiles {tiles()}")
        g = jax.grad(lambda *x: jnp.sum(kernel(*x) * probe),
                     argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(lambda *x: jnp.sum(golden(*x) * probe),
                         argnums=(0, 1, 2))(q, k, v)
        worst = max(rel_err(a, b_) for a, b_ in zip(g, g_ref))
        check(f"bwd {tag}", worst < 2e-2, f"rel={worst:.2e} tiles {tiles()}")


def main():
    from flexflow_tpu.utils.compilation_cache import enable_compilation_cache
    enable_compilation_cache()   # no FFModel.compile here to do it
    events.enable()              # flash.grid: the tiles of each call
    backend = jax.default_backend()
    print(f"backend={backend} devices={jax.devices()}", flush=True)
    if backend != "tpu":
        print("not a TPU — this script validates the compiled path only")
        return 2

    rng = np.random.default_rng(0)

    # MXU default precision is a SINGLE bf16 pass even for fp32 inputs —
    # both the Pallas kernel and the XLA oracle round their matmul
    # operands to bf16, but with different accumulation orders (online
    # softmax vs one-shot), so fp32-on-TPU agreement is bounded by bf16
    # rounding (~1e-2), not fp32 eps. Measured r4 on v5e: fwd <=3.1e-3,
    # bwd <=7.8e-3. The diagnostic below quantifies the hardware
    # rounding itself: oracle@default vs oracle@HIGHEST (3-pass fp32).
    b0, h0, s0, d0 = 2, 4, 512, 64
    qd = jnp.asarray(rng.normal(size=(b0, h0, s0, d0)), jnp.float32)
    kd = jnp.asarray(rng.normal(size=(b0, h0, s0, d0)), jnp.float32)
    vd = jnp.asarray(rng.normal(size=(b0, h0, s0, d0)), jnp.float32)
    o_def = mha_reference(qd, kd, vd)
    o_hi = mha_reference(qd, kd, vd, precision=jax.lax.Precision.HIGHEST)
    mxu_rel = rel_err(o_def, o_hi)
    print(f"INFO mxu default-vs-HIGHEST oracle rel={mxu_rel:.2e} "
          f"(fp32 tolerance floor on this hardware)", flush=True)

    # -- 1/2: numerics + grads ------------------------------------------
    # f32 covers the padded-seq case too; bf16 covers block-aligned only
    # (each (dtype, causal, seq) combo is ~2 compiles — keep it lean)
    for dtype, tol_f, tol_g, seqs in (
            (jnp.float32, 1e-2, 2e-2, (512, 393)),
            (jnp.bfloat16, 2e-2, 4e-2, (512,))):
        for causal in (False, True):
            for seq in seqs:
                b, h, d = 2, 4, 64
                q = jnp.asarray(rng.normal(size=(b, h, seq, d)), dtype)
                k = jnp.asarray(rng.normal(size=(b, h, seq, d)), dtype)
                v = jnp.asarray(rng.normal(size=(b, h, seq, d)), dtype)
                tag = f"{dtype.__name__}/causal={causal}/seq={seq}"

                o = flash_attention(q, k, v, causal=causal)
                o_ref = mha_reference(q, k, v, causal=causal)
                check(f"fwd {tag}", rel_err(o, o_ref) < tol_f,
                      f"rel={rel_err(o, o_ref):.2e} tiles {tiles()}")

                def loss(f, a, b_, c):
                    return jnp.sum(
                        f(a, b_, c, causal=causal).astype(jnp.float32) ** 2)

                g = jax.grad(lambda *x: loss(flash_attention, *x),
                             argnums=(0, 1, 2))(q, k, v)
                g_ref = jax.grad(lambda *x: loss(mha_reference, *x),
                                 argnums=(0, 1, 2))(q, k, v)
                worst = max(rel_err(a, b_) for a, b_ in zip(g, g_ref))
                check(f"bwd {tag}", worst < tol_g,
                      f"rel={worst:.2e} tiles {tiles()}")

    # the benchmark's cell 2: 12 x 12 heads of 1024 x 64, bf16, causal,
    # against the golden at HIGHEST precision, bf16 tolerances as above
    q, k, v = (jnp.asarray(rng.normal(size=(12, 12, 1024, 64)), jnp.bfloat16)
               for _ in range(3))

    def loss2(f, a, b_, c, **kw):
        return jnp.sum(f(a, b_, c, causal=True, **kw).astype(jnp.float32)
                       ** 2)

    hi = dict(precision=jax.lax.Precision.HIGHEST)
    o = flash_attention(q, k, v, causal=True)
    rel = rel_err(o, mha_reference(q, k, v, causal=True, **hi))
    check("fwd cell2 vs HIGHEST", rel < 2e-2,
          f"rel={rel:.2e} tiles {tiles()}")
    g = jax.grad(lambda *x: loss2(flash_attention, *x),
                 argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *x: loss2(mha_reference, *x, **hi),
                     argnums=(0, 1, 2))(q, k, v)
    rels = [rel_err(a, b_) for a, b_ in zip(g, g_ref)]
    check("bwd cell2 vs HIGHEST", max(rels) < 4e-2,
          "rel dq={:.2e} dk={:.2e} dv={:.2e} tiles ".format(*rels)
          + tiles())

    # the benchmark's cell 3: 32 heads of 4096 x 192 / 128, bf16, causal;
    # the forward alone (examples/tpu_validate_latent_moe.py holds the
    # gradients at this shape to the same golden)
    # (draws of its own, so the sections below read the data they read
    # before this check came)
    rng3 = np.random.default_rng(3)
    q, k = (jnp.asarray(rng3.normal(size=(1, 32, 4096, 192)), jnp.bfloat16)
            for _ in range(2))
    v = jnp.asarray(rng3.normal(size=(1, 32, 4096, 128)), jnp.bfloat16)
    o = flash_attention(q, k, v, causal=True)
    rel = rel_err(o, mha_reference(q, k, v, causal=True, **hi))
    check("fwd cell3 vs HIGHEST", rel < 2e-2,
          f"rel={rel:.2e} tiles {tiles()}")

    # -- 3: in-kernel dropout (TPU-only path) ---------------------------
    # seq 1024: a (512, 512) forward under backward tiles of another
    # shape, which have to regenerate the same mask
    b, h, seq, d = 2, 4, 1024, 64
    rate = 0.2
    q = jnp.asarray(rng.normal(size=(b, h, seq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, seq, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, seq, d)), jnp.float32)

    o1 = flash_attention(q, k, v, dropout_rate=rate, dropout_seed=7)
    o2 = flash_attention(q, k, v, dropout_rate=rate, dropout_seed=7)
    check("dropout deterministic (same seed)",
          bool(jnp.array_equal(o1, o2)))
    o3 = flash_attention(q, k, v, dropout_rate=rate, dropout_seed=8)
    check("dropout varies across seeds",
          not bool(jnp.array_equal(o1, o3)))

    # keep-rate: with v = all-ones columns the output row is
    # sum(keep*p/(1-r))/sum(p); its mean over rows ≈ 1
    ones_v = jnp.ones_like(v)
    od = flash_attention(q, k, ones_v, dropout_rate=rate, dropout_seed=3)
    mean_keep = float(jnp.mean(od))
    check("dropout keep-rate ~ E=1", abs(mean_keep - 1.0) < 0.05,
          f"mean={mean_keep:.4f}")

    # vjp consistency: the keep mask is a pure position hash, so the
    # exact mask is computable in plain XLA (dropout_keep_mask) and the
    # kernel's grads can be checked against jax.grad of an explicit-
    # masked golden. (Finite differences are useless here: MXU default
    # precision rounds inputs to bf16, whose ~8e-3 resolution swallows
    # an eps-sized perturbation — measured rel ~1 in the r4 runs even
    # though compiled-vs-interpret grads agreed to 1e-4. fp32 fd runs
    # in CPU CI: tests/test_kernels.py.)
    from flexflow_tpu.kernels import dropout_keep_mask

    def golden(qv, kv, vv):
        import math as _m
        sc = 1.0 / _m.sqrt(d)
        s = (jnp.einsum("bhqd,bhkd->bhqk", qv, kv,
                        precision=jax.lax.Precision.HIGHEST)
             .astype(jnp.float32) * sc)
        p = jax.nn.softmax(s, axis=-1)
        keep = dropout_keep_mask(b, h, seq, seq, rate, 11)
        p_eff = jnp.where(keep, p / (1.0 - rate), 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p_eff, vv,
                          precision=jax.lax.Precision.HIGHEST)

    probe = jnp.asarray(rng.normal(size=(b, h, seq, d)), jnp.float32)

    def loss_k(*x):
        return jnp.sum(flash_attention(
            *x, dropout_rate=rate, dropout_seed=11).astype(jnp.float32)
            * probe)

    def loss_g(*x):
        return jnp.sum(golden(*x) * probe)

    o_k = flash_attention(q, k, v, dropout_rate=rate, dropout_seed=11)
    rel = rel_err(o_k, golden(q, k, v))
    check("dropout fwd vs explicit-mask golden", rel < 1e-2,
          f"rel={rel:.2e}")
    g_k = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    g_g = jax.grad(loss_g, argnums=(0, 1, 2))(q, k, v)
    worst = max(rel_err(a, b_) for a, b_ in zip(g_k, g_g))
    check("dropout vjp vs explicit-mask golden", worst < 2e-2,
          f"rel={worst:.2e} tiles {tiles()}")

    cell1_layer(rng)
    long_f32_dropout()

    print(f"\n{len(FAILED)} failures" if FAILED else "\nALL PASS")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
