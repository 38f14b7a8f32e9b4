#!/usr/bin/env python
"""Compile every shipped Pallas kernel on the TPU and compare it with its
reference, forward and backward, at one caller shape each.

    python scripts/chip_kernel_check.py [--only SUBSTR]... [--provoke]
                                        [--out chiprun_out/kernel_check.json]

Cases (ISSUE 21, tentpole 6): the fused LSTM at the motion default
(H=32, batch 1440, T=128, f32 - ``main.py``'s defaults), the fused LSTM
and GRU at H=512 in f32 and bf16 (the largest hidden size ``auto``
routes to the kernel; shape of the launcher's char-LM chip row: batch
256, T=128), and flash attention forward / dQ / dK,dV at the attention
family's CLI defaults (hidden 32 over 4 heads -> head_dim 8, batch 1440,
T=128), and the same kernels with a value width of their own at the latent
attention cell's shape (32 heads, T=4096, q / k 192 wide, v 128 wide, causal,
f32; once more at JAX's default precision, where the kernel's f32 products
are bf16 passes and the bf16 tolerance applies; and a sweep that times the
three kernels alone there over a list of tiles, beside what the block
schedule says each tile costs in grid steps and fetched bytes), the same
kernels at the short-convolution decoder cell's shape (2 windows x 32 heads,
T=8192, q / k / v 64 wide: half a lane tile; the dense side a few heads at
a time, the whole score matrix of 64 rows would be 17 GB), and the
held experts' grouped products at the three decoder cells' shapes
(``ops/pallas_grouped.py``: forward, dlhs and drhs against
``jax.lax.ragged_dot`` and its own gradients, at JAX's default precision
and at "highest", ``sizes`` drawn as a fresh router draws them and padded
as ``held_experts_ffn`` pads them, ms a call of each side, both sides'
distance from a float64 host product; then a tile sweep).  Everything
else runs under
``jax.default_matmul_precision("highest")``
and the reference always computes in float32 - on the kernel's own
inputs, upcast - so it is the exact side of the comparison also for the
bf16 cases (a bf16 ``lax.scan`` reference accumulates its bias gradient
over T steps in bf16 and is itself off by several percent).

The script refuses to run without a TPU (a CPU run would interpret the
kernels, which proves nothing about Mosaic) and exits non-zero when any
case fails to compile or misses its tolerance.  ``--only`` keeps the
cases whose name contains SUBSTR (``chip_smoke.py`` runs the motion
case alone).  ``--provoke`` also makes the compiler refuse programs on
purpose and records what it says - the text
``Trainer._COMPILE_FAILURE_MARKS`` is based on.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

# max |kernel - reference| / max |reference| per compared array.  f32:
# the kernels' measured error is <= 5e-5 (flash dQ), so 2e-4 leaves no
# room for a bf16 pass.  bf16: 8 mantissa bits (eps 3.9e-3); measured
# <= 8.0e-3 (flash dQ) and <= 5.2e-3 on the RNN kernels through 128
# dependent steps, so 2e-2 is 2.5x the worst case seen.
TOLERANCE = {"float32": 2e-4, "bfloat16": 2e-2}


def _rel_err(got, want) -> float:
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    return float(jnp.max(jnp.abs(got - want))) / scale


def _compare(name, fused_fn, ref_fn, args, dtype):
    """Run value+grad of both sides on the same inputs; returns the row."""
    import jax
    import jax.numpy as jnp

    def scalarize(fn):
        def loss(*a):
            out = fn(*a)
            # a fixed non-uniform cotangent so every output element's
            # gradient path is exercised with a distinct weight
            w = jnp.linspace(0.5, 1.5, out.size, dtype=jnp.float32)
            return jnp.sum(out.astype(jnp.float32).reshape(-1) * w), out

        return jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(len(args))), has_aux=True))

    row = {"case": name, "dtype": dtype}
    t0 = time.perf_counter()
    (_, out_f), grads_f = jax.block_until_ready(scalarize(fused_fn)(*args))
    row["fused_compile_and_run_s"] = round(time.perf_counter() - t0, 2)
    ref_args = jax.tree.map(lambda a: a.astype(jnp.float32), args)
    (_, out_r), grads_r = jax.block_until_ready(
        scalarize(ref_fn)(*ref_args))
    errs = {"out": _rel_err(out_f, out_r)}
    flat_f, _ = jax.tree.flatten(grads_f)
    flat_r, _ = jax.tree.flatten(grads_r)
    for i, (gf, gr) in enumerate(zip(flat_f, flat_r)):
        errs[f"grad{i}"] = _rel_err(gf, gr)
    row["rel_err"] = {k: float(f"{v:.3e}") for k, v in errs.items()}
    row["finite"] = bool(all(
        bool(jnp.all(jnp.isfinite(jnp.asarray(g, jnp.float32))))
        for g in [out_f, *flat_f]))
    row["ok"] = row["finite"] and max(errs.values()) <= TOLERANCE[dtype]
    return row


def _rnn_case(cell, hidden, batch, seq, in_dim, dtype_name):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_rnn_tpu.ops import pallas_rnn, rnn

    dtype = jnp.dtype(dtype_name)
    init = {"lstm": rnn.init_lstm_layer, "gru": rnn.init_gru_layer}[cell]
    params = jax.tree.map(
        lambda p: p.astype(dtype),
        init(jax.random.PRNGKey(0), in_dim, hidden))
    x = jax.random.normal(
        jax.random.PRNGKey(1), (batch, seq, in_dim), jnp.float32
    ).astype(dtype)
    fused = {"lstm": pallas_rnn.lstm_layer_fused,
             "gru": pallas_rnn.gru_layer_fused}[cell]
    ref = {"lstm": rnn.lstm_layer, "gru": rnn.gru_layer}[cell]
    name = f"{cell}_fused h{hidden} b{batch} t{seq} in{in_dim}"
    row = _compare(name, lambda p, xx: fused(p, xx)[0],
                   lambda p, xx: ref(p, xx)[0], (params, x), dtype_name)
    block_b = pallas_rnn._pick_block_b(batch, hidden, dtype.itemsize)
    row["block_b"] = block_b
    # any padded row costs a padded copy of the layer's (T, B, 4H) arrays
    row["padded_rows"] = -batch % block_b
    return row


def _flash_case(batch, heads, seq, head_dim, dtype_name, *, v_dim=None,
                causal=False, precision="highest", dense_heads=None):
    """``precision`` is the ambient matmul precision of the KERNEL's side
    (the reference always runs at "highest"); below "highest" an f32
    kernel multiplies in bf16 passes and is held to the bf16 tolerance.
    ``dense_heads``: the dense side computes that many heads' score
    matrices at a time, one block after another, where all of them at once
    do not fit the chip."""
    import functools

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_rnn_tpu.ops.attention import mha_attention
    from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
        flash_attention,
    )

    dtype = jnp.dtype(dtype_name)
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i),
                          (batch, heads, seq, width),
                          jnp.float32).astype(dtype)
        for i, width in enumerate((head_dim, head_dim, v_dim or head_dim))
    )
    name = (f"flash_attention b{batch} h{heads} t{seq} d{head_dim}"
            + (f" v{v_dim} causal {precision}" if v_dim else ""))

    def fused(*args):
        with jax.default_matmul_precision(precision):
            return flash_attention(*args, causal=causal)

    dense = functools.partial(mha_attention, causal=causal)

    @jax.checkpoint
    def some_heads(block):
        return dense(*(a[None] for a in block))[0]

    def dense_in_blocks(*args):
        blocks = [a.reshape(-1, dense_heads, *a.shape[2:]) for a in args]
        out = jax.lax.map(some_heads, tuple(blocks))
        return out.reshape(batch, heads, seq, out.shape[-1])

    # grad0/grad1/grad2 = the dQ kernel and the two outputs of the dK,dV
    # kernel
    return _compare(name, fused, dense_in_blocks if dense_heads else dense,
                    (q, k, v),
                    dtype_name if precision == "highest" else "bfloat16")


# (block_q, block_k) the sweep times each kernel at, besides the picker's own
SWEEP_TILES = ((256, 256), (512, 512), (1024, 256), (256, 1024), (1024, 512),
               (512, 1024), (1024, 1024), (2048, 512), (512, 2048))


def _flash_sweep(batch, heads, seq, head_dim, v_dim, *, tiles=SWEEP_TILES,
                 calls=5):
    """Time forward, dq and dk / dv each alone (causal, f32, JAX's default
    precision: the trainer's) at the picker's tile and at every tile of
    ``tiles`` the compiler takes; one row a (kernel, tile) with ms a call
    beside the schedule's grid steps and fetched bytes for the whole call.
    Measures, compares nothing: ``ok`` says only that the picker's tiles
    compiled and ran."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_rnn_tpu.ops import pallas_attention as pa

    rows_n = batch * heads
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(key, (rows_n, seq, head_dim), jnp.float32)
            for key in keys[:2])
    v, do = (jax.random.normal(key, (rows_n, seq, v_dim), jnp.float32)
             for key in keys[2:])
    offsets = jnp.zeros((2,), jnp.int32)

    def run(kind, block_q, block_k, *residuals):
        operands = (q, k, v) if kind == "fwd" else (q, k, v, do, *residuals)
        fn = jax.jit(lambda *a: pa._call(
            kind, a, offsets, True, block_q, block_k, seq, seq, "sweep"))
        out = jax.block_until_ready(fn(*operands))  # compiles
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*operands)
        jax.block_until_ready(out)
        return out, 1e3 * (time.perf_counter() - t0) / calls

    table, ok = [], True
    with jax.default_matmul_precision("default"):
        (o, lse), _ = run("fwd", None, None)
        delta = pa._delta_of(do, o)
        for kind in ("fwd", "dq", "dkv"):
            picked = pa.pick_blocks(kind, seq, seq, head_dim, v_dim, 4)[:2]
            for tile in dict.fromkeys((picked, *tiles)):
                counts = pa.schedule(kind, seq, seq, *tile, causal=True)
                row = {
                    "kernel": kind, "tile": list(tile),
                    "picked": tile == picked,
                    "grid_steps": rows_n * counts["steps"],
                    "computed_blocks": rows_n * counts["computed"],
                    "fetched_gb": round(rows_n * pa.fetched_bytes(
                        kind, seq, seq, *tile, head_dim, v_dim, 4,
                        causal=True) / 1e9, 3),
                    "vmem_model_mib": round(pa.vmem_bytes(
                        kind, *tile, head_dim, v_dim, 4) / 2 ** 20, 2),
                }
                try:
                    row["ms_a_call"] = round(
                        run(kind, *tile, lse, delta)[1], 3)
                except Exception as exc:  # noqa: BLE001 - a refused tile
                    row["refused"] = str(exc)[-300:]
                    ok = ok and tile != picked
                table.append(row)
                print(json.dumps(row), flush=True)
    return {"case": f"flash_attention sweep b{batch} h{heads} t{seq} "
                    f"d{head_dim} v{v_dim} causal default",
            "dtype": "float32", "calls_timed": calls, "sweep": table,
            "ok": ok}


# the three decoder cells' grouped products: rows a layer (the expert layer's
# capacity), model width, expert width, held experts, experts in all, picks
# a token, tokens a step (benchmarks/configs/*_1of16.json, *_1of8.json)
GROUPED_SHAPES = {
    "hybrid_ssm_moe cell": dict(rows=12288, d=2688, f=1856, held=8,
                                experts=128, k=6, tokens=8192),
    "mla_moe cell": dict(rows=16384, d=2048, f=768, held=16,
                         experts=256, k=8, tokens=8192),
    "hybrid_ssm_moe conv cell": dict(rows=32768, d=2048, f=1536, held=8,
                                     experts=64, k=4, tokens=16384),
}
# (tm, share of the contraction, share of the width) the sweep times each
# kernel at, besides the picker's own
GROUPED_SWEEP = ((128, 1, 1), (256, 1, 1), (512, 1, 1), (1024, 1, 1),
                 (512, 1, 3), (512, 3, 1), (256, 1, 3), (1024, 1, 3))


def _router_sizes(shape, seed):
    """Group sizes as ``held_experts_ffn`` hands them to the products on
    fresh weights: sigmoid top-k over ALL experts of tokens that share most
    of their direction (so a few experts lead, as a fresh model's hidden
    states make them), the held experts' counts, the spare rows joined to
    the last group."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_rnn_tpu.ops.moe import route_sigmoid_topk

    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = (jax.random.normal(keys[0], (1, shape["d"]))
         + 0.5 * jax.random.normal(keys[1], (shape["tokens"], shape["d"])))
    router = 0.02 * jax.random.normal(keys[2], (shape["d"], shape["experts"]))
    picked, _ = route_sigmoid_topk(
        router, jnp.zeros((shape["experts"],)), x, shape["k"], 1.0)
    counts = jnp.bincount(picked.reshape(-1), length=shape["experts"])
    counts = counts[:shape["held"]]
    clipped = jnp.minimum(jnp.cumsum(counts), shape["rows"])
    sizes = jnp.diff(clipped, prepend=0)
    sizes = sizes.at[-1].add(shape["rows"] - clipped[-1])
    return sizes.astype(jnp.int32), counts


def _grouped_operands(cell, product, seed):
    """``((m, k, n, groups), sizes, held counts, (rows, weights, d_out))``
    of one grouped product of a decoder cell (``product``: ``up`` D -> F or
    ``down`` F -> D)."""
    import jax
    import jax.numpy as jnp

    shape = GROUPED_SHAPES[cell]
    m, groups = shape["rows"], shape["held"]
    k, n = ((shape["d"], shape["f"]) if product == "up"
            else (shape["f"], shape["d"]))
    sizes, counts = _router_sizes(shape, seed)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    rows = jax.random.normal(keys[0], (m, k), jnp.float32)
    weights = 0.02 * jax.random.normal(keys[1], (groups, k, n), jnp.float32)
    d_out = jax.random.normal(keys[2], (m, n), jnp.float32)
    return (m, k, n, groups), sizes, counts, (rows, weights, d_out)


def _timed(fn, *args, calls=10):
    import jax

    out = jax.block_until_ready(fn(*args))  # compiles
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, 1e3 * (time.perf_counter() - t0) / calls


def _grouped_case(cell, product, precision, seed=0):
    """One grouped product of a decoder cell (``product``: ``up`` D -> F
    or ``down`` F -> D), its three forms through ``ops/pallas_grouped.py``
    against ``jax.lax.ragged_dot`` and its own gradient, both under
    ``precision``: agreement, ms a call of each side, and both sides'
    distance from a float64 host product over 256 rows (one bf16 pass reads
    about 2e-3 of the largest entry, three about 1e-5)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_rnn_tpu.ops import pallas_grouped as pg

    (m, k, n, groups), sizes, counts, (rows, weights, d_out) = (
        _grouped_operands(cell, product, seed))

    def xla_vjp(which):
        def run(rows, weights, d_out):
            _, pull = jax.vjp(
                lambda r, w: jax.lax.ragged_dot(r, w, sizes), rows, weights)
            return pull(d_out)[which]
        return run

    sides = {
        "fwd": (lambda r, w, d: pg._gmm(r, w, sizes),
                lambda r, w, d: jax.lax.ragged_dot(r, w, sizes)),
        "dlhs": (lambda r, w, d: pg._gmm(d, w, sizes, transposed=True),
                 xla_vjp(0)),
        "drhs": (lambda r, w, d: pg._tgmm(r, d, sizes), xla_vjp(1)),
    }
    row = {"case": f"grouped {cell} {product} {precision}",
           "dtype": "float32", "m_k_n_groups": [m, k, n, groups],
           "held_rows": int(counts.sum()),
           "rows_max_over_mean": round(
               float(counts.max() / counts.mean()), 2),
           "gflop": round(2 * m * k * n / 1e9, 1)}
    kk = {"fwd": (k, n), "dlhs": (n, k), "drhs": (k, n)}
    names = {"fwd": pg.GMM, "dlhs": pg.DLHS, "drhs": pg.TGMM}
    errs = {}
    with jax.default_matmul_precision(precision):
        for form, (ours, xla) in sides.items():
            got, ours_ms = _timed(jax.jit(ours), rows, weights, d_out)
            want, xla_ms = _timed(jax.jit(xla), rows, weights, d_out)
            errs[form] = _rel_err(got, want)
            row[f"{form}_ms"] = {"kernel": round(ours_ms, 3),
                                 "ragged_dot": round(xla_ms, 3)}
            row[f"{form}_tiles"] = list(pg.pick_tiles(
                names[form], m, *kk[form], groups, 4)[:3])
            if form == "fwd":
                # rows of the first and of the last group against float64
                take = np.r_[0:128, m - 128:m]
                group = np.searchsorted(
                    np.cumsum(np.asarray(sizes)), take, side="right")
                exact = np.einsum(
                    "mk,mkn->mn", np.asarray(rows, np.float64)[take],
                    np.asarray(weights, np.float64)[group])
                row["off_float64"] = {
                    "kernel": float(f"{_rel_err(got[take], exact):.3e}"),
                    "ragged_dot": float(
                        f"{_rel_err(want[take], exact):.3e}")}
    row["rel_err"] = {k_: float(f"{v:.3e}") for k_, v in errs.items()}
    # two sides that each make bf16 passes differ by a bf16 rounding
    tolerance = TOLERANCE[
        "float32" if precision == "highest" else "bfloat16"]
    row["ok"] = max(errs.values()) <= tolerance
    return row


def _grouped_sweep(cell, product, calls=10):
    """Time the three kernels alone at JAX's default precision (the
    trainer's) at the picker's tiles and at ``GROUPED_SWEEP``'s; one row a
    (kernel, tile) with ms a call beside the VMEM model.  Measures,
    compares nothing: ``ok`` says that the picker's tiles ran."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_rnn_tpu.ops import pallas_grouped as pg

    (m, k, n, groups), sizes, _, (rows, weights, d_out) = (
        _grouped_operands(cell, product, 0))
    runs = {
        pg.GMM: (k, n, lambda t: pg._gmm(rows, weights, sizes, tiles=t)),
        pg.DLHS: (n, k, lambda t: pg._gmm(
            d_out, weights, sizes, transposed=True, tiles=t)),
        pg.TGMM: (k, n, lambda t: pg._tgmm(rows, d_out, sizes, tiles=t)),
    }
    table, ok = [], True
    with jax.default_matmul_precision("default"):
        for kind, (kk, nn, run) in runs.items():
            picked = pg.pick_tiles(kind, m, kk, nn, groups, 4)[:3]
            tiles = [picked]
            for tm, k_parts, n_parts in GROUPED_SWEEP:
                tk = kk // k_parts
                tn = -(-nn // n_parts // 128) * 128 if n_parts > 1 else nn
                if kk % k_parts == 0 and (tk % 128 == 0 or k_parts == 1):
                    tiles.append((tm, tk, tn))
            for tile in dict.fromkeys(tiles):
                row = {"kernel": kind, "tile": list(tile),
                       "picked": tile == picked,
                       "vmem_model_mib": round(pg.vmem_bytes(
                           kind, *tile, kk, 4) / 2 ** 20, 1)}
                try:
                    row["ms_a_call"] = round(
                        _timed(lambda t=tile: run(t), calls=calls)[1], 3)
                except Exception as exc:  # noqa: BLE001 - a refused tile
                    row["refused"] = str(exc)[-300:]
                    ok = ok and tile != picked
                table.append(row)
                print(json.dumps(row), flush=True)
    return {"case": f"grouped sweep {cell} {product} default",
            "dtype": "float32", "calls_timed": calls, "sweep": table,
            "ok": ok}


def _provoke_refusals():
    """Make the installed compiler refuse programs and return what it
    says, verbatim (first 1500 characters)."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_rnn_tpu.ops import pallas_rnn, rnn
    from pytorch_distributed_rnn_tpu.training.base import Trainer

    rows = []

    def attempt(name, fn):
        try:
            jax.block_until_ready(fn())
            rows.append({"provoked": name, "refused": False})
        except Exception as exc:  # noqa: BLE001 - the message is the result
            rows.append({
                "provoked": name, "refused": True,
                "type": type(exc).__name__,
                "message": str(exc)[:1500],
                "is_compile_failure": Trainer.is_compile_failure(exc),
            })

    # 1. Mosaic scoped-VMEM overflow: the fused LSTM backward at a batch
    #    tile the VMEM model would never pick
    params = rnn.init_lstm_layer(jax.random.PRNGKey(0), 512, 512)
    x = jnp.zeros((1024, 16, 512), jnp.float32)
    attempt("pallas scoped-vmem overflow (lstm bwd h512 f32 block_b 1024)",
            lambda: jax.jit(jax.grad(
                lambda p: jnp.sum(pallas_rnn.lstm_layer_fused(
                    p, x, block_b=1024)[0])))(params))

    # 2. XLA HBM exhaustion at compile time: three live 9 GiB matrices
    #    on a 16 GiB chip.  Ahead-of-time from abstract shapes, so
    #    nothing is allocated or executed - the refusal is the compiler's
    spec = jax.ShapeDtypeStruct((49152, 49152), jnp.float32)
    attempt("xla hbm exhaustion (3 x 9 GiB live, compile only)",
            lambda: jax.jit(lambda a: (a @ a) @ (a.T @ a))
            .lower(spec).compile() and None)

    # 3. a kernel Mosaic itself rejects: fp32 contract precision with a
    #    bf16 operand (what the fused RNN kernels asked for in bf16 under
    #    "highest" before ops/pallas_rnn.py:_mxu_dot)
    from jax.experimental import pallas as pl

    def mixed_dot(a_ref, b_ref, o_ref):
        o_ref[:] = jax.lax.dot_general(
            a_ref[:], b_ref[:], (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    attempt("mosaic refusal (f32 x bf16 matmul at fp32 precision)",
            lambda: pl.pallas_call(
                mixed_dot,
                out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            )(jnp.ones((128, 128), jnp.float32),
              jnp.ones((128, 128), jnp.bfloat16)))
    return rows


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="chip_kernel_check.py")
    parser.add_argument("--out", default="chiprun_out/kernel_check.json")
    parser.add_argument("--only", action="append", metavar="SUBSTR",
                        help="run only the cases whose name contains "
                        "SUBSTR (given several times: any of them)")
    parser.add_argument("--provoke", action="store_true",
                        help="also provoke compile refusals and record "
                        "the compiler's messages")
    args = parser.parse_args(argv)

    from pytorch_distributed_rnn_tpu.utils import apply_platform_overrides

    jax = apply_platform_overrides()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_kernel_check: no TPU found (jax reports platform "
              f"{device.platform!r}); the kernels would run interpreted",
              file=sys.stderr)
        return 1

    from pytorch_distributed_rnn_tpu.ops.pallas_rnn import _interpret

    assert not _interpret(), "interpret mode selected on a TPU"

    cases = {
        "lstm h32 f32 (motion default)":
            lambda: _rnn_case("lstm", 32, 1440, 128, 9, "float32"),
        "lstm h512 f32":
            lambda: _rnn_case("lstm", 512, 256, 128, 512, "float32"),
        "lstm h512 bf16":
            lambda: _rnn_case("lstm", 512, 256, 128, 512, "bfloat16"),
        "gru h512 f32":
            lambda: _rnn_case("gru", 512, 256, 128, 512, "float32"),
        "gru h512 bf16":
            lambda: _rnn_case("gru", 512, 256, 128, 512, "bfloat16"),
        "flash attention f32 (attention default)":
            lambda: _flash_case(1440, 4, 128, 8, "float32"),
        "flash attention bf16":
            lambda: _flash_case(1440, 4, 128, 8, "bfloat16"),
        "flash attention latent f32 highest (mla_moe cell)":
            lambda: _flash_case(1, 32, 4096, 192, "float32", v_dim=128,
                                causal=True),
        "flash attention latent f32 default (mla_moe cell)":
            lambda: _flash_case(1, 32, 4096, 192, "float32", v_dim=128,
                                causal=True, precision="default"),
        "flash attention latent f32 default tile sweep (mla_moe cell)":
            lambda: _flash_sweep(2, 32, 4096, 192, 128),
        "flash attention head 64 f32 highest (hybrid_ssm_moe conv cell)":
            lambda: _flash_case(2, 32, 8192, 64, "float32", v_dim=64,
                                causal=True, dense_heads=2),
        "flash attention head 64 f32 default (hybrid_ssm_moe conv cell)":
            lambda: _flash_case(2, 32, 8192, 64, "float32", v_dim=64,
                                causal=True, precision="default",
                                dense_heads=2),
        "flash attention head 64 f32 default tile sweep "
        "(hybrid_ssm_moe conv cell)":
            lambda: _flash_sweep(2, 32, 8192, 64, 64, tiles=(
                (512, 512), (1024, 512), (512, 1024), (2048, 1024))),
    }
    for cell in GROUPED_SHAPES:
        for product in ("up", "down"):
            for precision in ("default", "highest"):
                cases[f"grouped {cell} {product} {precision}"] = (
                    lambda c=cell, p=product, q=precision:
                    _grouped_case(c, p, q))
            cases[f"grouped sweep {cell} {product}"] = (
                lambda c=cell, p=product: _grouped_sweep(c, p))
    only = args.only or [""]
    cases = {k: v for k, v in cases.items() if any(o in k for o in only)}
    if not cases:
        parser.error(f"--only {args.only!r} matches no case")
    rows = []
    with jax.default_matmul_precision("highest"):
        for name, case in cases.items():
            try:
                rows.append(case())
            except Exception as exc:  # noqa: BLE001 - recorded, fails the run
                rows.append({"case": name, "ok": False,
                             "type": type(exc).__name__,
                             "error": str(exc)[:3000]})
            print(json.dumps(rows[-1]), flush=True)
    report = {
        "device": {"platform": device.platform,
                   "kind": device.device_kind,
                   "count": len(jax.devices())},
        "jax": jax.__version__,
        "tolerance": TOLERANCE,
        "cases": rows,
    }
    if args.provoke:
        report["refusals"] = _provoke_refusals()
        for row in report["refusals"]:
            print(json.dumps(row), flush=True)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=1))
    ok = all(row.get("ok") for row in rows)
    print(json.dumps({"ok": ok, "device": report["device"]}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
