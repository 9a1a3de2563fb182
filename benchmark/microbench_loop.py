"""In-program kernel throughput: amortizes per-dispatch latency by running
each op N times inside ONE jitted fori_loop — the same regime as the real
decode while_loop."""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ipex_llm_tpu.ops.linear import qmatmul_reference
from ipex_llm_tpu.ops.pallas.qmatmul import qmatmul_pallas
from ipex_llm_tpu.ops.pallas.decode_attention import decode_sdpa
from ipex_llm_tpu.ops.attention import sdpa_reference

ITERS = 64


def timed(f, *args):
    out = jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / ITERS


def bench_qmatmul(m, k, n, qtype="sym_int4"):
    from ipex_llm_tpu.quantize import quantize

    rng = np.random.default_rng(0)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        qt = quantize((rng.standard_normal((k, n)) * 0.02).astype(np.float32),
                      qtype)
    dev = [d for d in jax.devices() if d.platform != "cpu"]
    if dev:
        qt = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, dev[0]) if hasattr(x, "shape") else x,
            qt)

    def make(fn):
        @jax.jit
        def run(seed):
            def body(i, acc):
                x = jnp.full((m, k), seed + i, jnp.bfloat16)
                return acc + fn(x, qt)[0, 0].astype(jnp.float32)
            return jax.lax.fori_loop(0, ITERS, body, 0.0)
        return run

    bytes_per = qt.nbytes + m * k * 2 + m * n * 4
    tp = timed(make(qmatmul_pallas), jnp.asarray(1.0, jnp.bfloat16))
    tr = timed(make(qmatmul_reference), jnp.asarray(1.0, jnp.bfloat16))
    print(f"qmatmul {qtype} M={m} [{k}x{n}]: pallas {tp*1e6:7.1f}us "
          f"({bytes_per/tp/1e9:6.1f} GB/s) | xla {tr*1e6:7.1f}us "
          f"({bytes_per/tr/1e9:6.1f} GB/s)", flush=True)


def bench_decode_attn(b, hq, hkv, s, d, dtype=jnp.bfloat16):
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32).astype(dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32).astype(dtype)
    kv_len = jnp.full((b,), s, jnp.int32)
    kv_start = jnp.zeros((b,), jnp.int32)
    nbytes = 2 * b * hkv * s * d * k.dtype.itemsize

    def kern(q, k, v):
        return decode_sdpa(q, k, v, kv_len=kv_len, kv_start=kv_start)

    def ref(q, k, v):
        kd = k.astype(jnp.bfloat16).transpose(0, 2, 1, 3)
        vd = v.astype(jnp.bfloat16).transpose(0, 2, 1, 3)
        qpos = (kv_len - 1)[:, None]
        return sdpa_reference(q, kd, vd, causal=True, q_positions=qpos,
                              kv_len=kv_len, kv_start=kv_start)

    def make(fn):
        @jax.jit
        def run(seed):
            def body(i, acc):
                q = jnp.full((b, 1, hq, d), seed + i, jnp.bfloat16)
                return acc + fn(q, k, v)[0, 0, 0, 0].astype(jnp.float32)
            return jax.lax.fori_loop(0, ITERS, body, 0.0)
        return run

    tk = timed(make(kern), jnp.asarray(1.0, jnp.bfloat16))
    tr = timed(make(ref), jnp.asarray(1.0, jnp.bfloat16))
    print(f"decode_attn B={b} Hq={hq} Hkv={hkv} S={s} D={d} {k.dtype}: "
          f"kernel {tk*1e6:7.1f}us ({nbytes/tk/1e9:6.1f} GB/s) | "
          f"xla {tr*1e6:7.1f}us ({nbytes/tr/1e9:6.1f} GB/s)", flush=True)


if __name__ == "__main__":
    d0 = jax.devices()[0]
    print("backend:", jax.default_backend(), "| device:", d0.device_kind,
          flush=True)
    bench_qmatmul(1, 4096, 12288)
    bench_qmatmul(1, 4096, 22016)
    bench_qmatmul(1, 11008, 4096)
    bench_qmatmul(1, 4096, 32000)
    bench_qmatmul(16, 4096, 22016)
    bench_decode_attn(1, 32, 32, 1280, 128)
    bench_decode_attn(1, 32, 8, 4096, 128)
    bench_decode_attn(1, 32, 8, 4096, 128, jnp.float8_e5m2)
