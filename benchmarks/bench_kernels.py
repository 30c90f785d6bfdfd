"""Kernel micro-bench: wall time of each Pallas kernel (interpret mode on
CPU — correctness-path timing) vs its jnp oracle, plus the analytic TPU
roofline time of the kernel's tiling (the number that matters for the
ACCEL target; see EXPERIMENTS.md §Perf).
"""
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.kernels import ops, ref
from repro.launch.roofline import HBM_BW, PEAK_FLOPS
from repro.launch import compile_cache


def _time(fn, *args, reps: int = 3) -> float:
    fn(*args)  # warm up / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def main() -> None:
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)

    # flash attention: B=1,S=1024,H=4,hd=128
    B, S, H, hd = 1, 1024, 4, 128
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32)
    us_ref = _time(lambda: jax.jit(ref.attention_ref)(
        q.transpose(0, 2, 1, 3).reshape(B * H, S, hd),
        k.transpose(0, 2, 1, 3).reshape(B * H, S, hd),
        v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)))
    flops = 4 * B * H * S * S * hd
    tpu_us = flops / PEAK_FLOPS * 1e6
    emit("kernels/flash_attention_ref", us_ref,
         f"oracle; tpu_roofline={tpu_us:.1f}us for {flops/1e9:.2f}GF")
    us_k = _time(lambda: ops.flash_attention(q, k, v, block_q=256,
                                             block_k=256))
    emit("kernels/flash_attention_pallas_interp", us_k,
         "interpret-mode correctness path")

    # ssd scan
    B2, S2, H2, P2, N2 = 2, 512, 4, 64, 32
    x = jax.random.normal(ks[3], (B2, S2, H2, P2))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (B2, S2, H2)))
    A = -jnp.exp(jax.random.normal(ks[5], (H2,)) * 0.5)
    Bm = jax.random.normal(ks[6], (B2, S2, N2))
    Cm = jax.random.normal(ks[7], (B2, S2, N2))
    from repro.models.ssm import ssd_chunked
    us_ref = _time(lambda: jax.jit(
        lambda *a: ssd_chunked(*a, chunk=128))(x, dt, A, Bm, Cm))
    emit("kernels/ssd_ref", us_ref, "oracle (chunked jnp)")
    us_k = _time(lambda: ops.ssd_scan(x, dt, A, Bm, Cm, chunk=128))
    emit("kernels/ssd_pallas_interp", us_k, "interpret-mode")

    # grouped matmul
    E, C, D, F = 8, 256, 512, 512
    xg = jax.random.normal(ks[0], (E, C, D), jnp.bfloat16)
    wg = jax.random.normal(ks[1], (E, D, F), jnp.bfloat16)
    gs = jnp.full((E,), C, jnp.int32)
    us_ref = _time(lambda: jax.jit(ref.grouped_matmul_ref)(xg, wg, gs))
    gf = 2 * E * C * D * F / 1e9
    emit("kernels/moe_gmm_ref", us_ref,
         f"oracle; tpu_roofline={2*E*C*D*F/PEAK_FLOPS*1e6:.1f}us for {gf:.2f}GF")

    # rmsnorm
    xr = jax.random.normal(ks[2], (4096, 1024))
    wr = jax.random.normal(ks[3], (1024,))
    us_ref = _time(lambda: jax.jit(ref.rmsnorm_ref)(xr, wr))
    bytes_moved = 2 * xr.size * 4
    emit("kernels/rmsnorm_ref", us_ref,
         f"oracle; tpu_roofline={bytes_moved/HBM_BW*1e6:.1f}us (bw-bound)")

    # knn digits (paper app)
    t = jax.random.randint(ks[4], (256, 7), 0, 2**31 - 1,
                           jnp.int32).astype(jnp.uint32)
    r = jax.random.randint(ks[5], (2048, 7), 0, 2**31 - 1,
                           jnp.int32).astype(jnp.uint32)
    lb = jax.random.randint(ks[6], (2048,), 0, 10, jnp.int32)
    us = _time(lambda: ops.knn_digits(t, r, lb))
    emit("kernels/knn_digits", us, "paper DigitRec function (interp)")

    # gqa decode (flash-decoding style)
    BH, Smax, hd2 = 4, 2048, 128
    qd = jax.random.normal(ks[1], (BH, 1, hd2))
    kd = jax.random.normal(ks[2], (BH, Smax, hd2))
    vd = jax.random.normal(ks[3], (BH, Smax, hd2))
    us_ref = _time(lambda: jax.jit(ref.decode_attention_ref)(
        qd, kd, vd, jnp.int32(Smax - 1)))
    cache_bytes = 2 * BH * Smax * hd2 * 4
    emit("kernels/gqa_decode_ref", us_ref,
         f"oracle; tpu_roofline={cache_bytes/HBM_BW*1e6:.1f}us (cache-read bound)")

    # haar window scorer (paper app)
    img = jax.random.normal(ks[7], (240, 320))
    feats = jax.random.normal(ks[0], (16, 24 * 24))
    us = _time(lambda: ops.window_scores(img, feats))
    emit("kernels/haar_window_320x240", us, "paper FaceDet function (interp)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
