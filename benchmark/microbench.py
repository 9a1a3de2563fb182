"""Kernel-level microbenchmarks on the real chip.

Times the decode-path hot ops in isolation (fused dequant-matmul at M=1,
decode attention) against their XLA fallbacks, reporting effective HBM
bandwidth — the decode roofline currency.  Run: python benchmark/microbench.py

``collect()`` returns the same numbers structured (reference peer: the
all-in-one harness's per-op CSV columns, dev/benchmark/all-in-one/run.py).
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ipex_llm_tpu.kv import PagedKVCache
from ipex_llm_tpu.ops.linear import qmatmul_reference
from ipex_llm_tpu.ops.pallas.qmatmul import qmatmul_pallas
from ipex_llm_tpu.ops.pallas.decode_attention import decode_sdpa
from ipex_llm_tpu.ops.pallas.ragged_paged_attention import ragged_paged_sdpa
from ipex_llm_tpu.ops.attention import sdpa_reference
from ipex_llm_tpu.quantize import quantize


def timeit(fn, *args, iters=50):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_qmatmul(m, k, n, qtype="sym_int4", iters=50):
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
    x = jnp.ones((m, k), jnp.bfloat16)
    if dev:
        x = jax.device_put(x, dev[0])

    bytes_w = qt.nbytes + m * k * 2 + m * n * 4
    f_pallas = jax.jit(lambda x: qmatmul_pallas(x, qt))
    f_ref = jax.jit(lambda x: qmatmul_reference(x, qt))
    tp = timeit(f_pallas, x, iters=iters)
    tr = timeit(f_ref, x, iters=iters)
    print(f"qmatmul {qtype} M={m} [{k}x{n}]: pallas {tp*1e6:8.1f}us "
          f"({bytes_w/tp/1e9:6.1f} GB/s) | xla {tr*1e6:8.1f}us "
          f"({bytes_w/tr/1e9:6.1f} GB/s)")
    return {"op": f"qmatmul_{qtype}_m{m}_{k}x{n}",
            "pallas_us": round(tp * 1e6, 1), "xla_us": round(tr * 1e6, 1),
            "pallas_gbs": round(bytes_w / tp / 1e9, 1),
            "xla_gbs": round(bytes_w / tr / 1e9, 1)}


def bench_decode_attn(b, hq, hkv, s, d, dtype=jnp.bfloat16, iters=50):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, 1, hq, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32).astype(dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32).astype(dtype)
    kv_len = jnp.full((b,), s, jnp.int32)
    kv_start = jnp.zeros((b,), jnp.int32)
    nbytes = 2 * b * hkv * s * d * k.dtype.itemsize

    f_kern = jax.jit(lambda q, k, v: decode_sdpa(q, k, v, kv_len=kv_len,
                                                 kv_start=kv_start))
    def ref(q, k, v):
        kd = k.astype(jnp.bfloat16).transpose(0, 2, 1, 3)
        vd = v.astype(jnp.bfloat16).transpose(0, 2, 1, 3)
        qpos = (kv_len - 1)[:, None]
        return sdpa_reference(q, kd, vd, causal=True, q_positions=qpos,
                              kv_len=kv_len, kv_start=kv_start)
    f_ref = jax.jit(ref)
    tk = timeit(f_kern, q, k, v, iters=iters)
    tr = timeit(f_ref, q, k, v, iters=iters)
    print(f"decode_attn B={b} Hq={hq} Hkv={hkv} S={s} D={d} {k.dtype}: "
          f"kernel {tk*1e6:8.1f}us ({nbytes/tk/1e9:6.1f} GB/s) | "
          f"xla {tr*1e6:8.1f}us ({nbytes/tr/1e9:6.1f} GB/s)")
    return {"op": f"decode_attn_b{b}_h{hq}/{hkv}_s{s}_d{d}_{k.dtype.name}",
            "pallas_us": round(tk * 1e6, 1), "xla_us": round(tr * 1e6, 1),
            "pallas_gbs": round(nbytes / tk / 1e9, 1),
            "xla_gbs": round(nbytes / tr / 1e9, 1)}


def _paged_fixture(r, hkv, maxp, ps, d, dtype):
    """A filled paged pool + per-row block tables: row i owns pages
    [1 + i*maxp, 1 + (i+1)*maxp) (page 0 is the engine's scratch page).
    The cache wraps the random pools directly — going through init would
    allocate equal-size zero pools that sit dead in HBM for the run."""
    rng = np.random.default_rng(0)
    n_pages = 1 + r * maxp
    tables = jnp.asarray(
        1 + np.arange(r * maxp, dtype=np.int32).reshape(r, maxp))
    k = jnp.asarray(rng.standard_normal((n_pages, hkv, ps, d)),
                    jnp.float32).astype(dtype)
    v = jnp.asarray(rng.standard_normal((n_pages, hkv, ps, d)),
                    jnp.float32).astype(dtype)
    cache = PagedKVCache(
        k=k[None], v=v[None], tables=tables,
        length=jnp.zeros((), jnp.int32),
        storage="fp8" if dtype == jnp.float8_e5m2 else "bf16")
    return cache, k, v


def bench_paged_gather(r, hkv, maxp, ps, d, dtype=jnp.bfloat16, iters=50):
    """The serving engine's XLA fallback read: pool layer -> head-major
    [R, H, maxP*ps, D] row view (kv.PagedKVCache.gather_layer).  An fp8
    pool gathers e5m2 codes — half the bytes of the bf16 gather this op
    is tracked against."""
    cache, k, _ = _paged_fixture(r, hkv, maxp, ps, d, dtype)
    nbytes = r * maxp * ps * hkv * d * k.dtype.itemsize
    f = jax.jit(lambda kl: cache.gather_layer(kl))
    t = timeit(f, k, iters=iters)
    print(f"paged_gather R={r} Hkv={hkv} P={maxp}x{ps} D={d} {k.dtype}: "
          f"xla {t*1e6:8.1f}us ({nbytes/t/1e9:6.1f} GB/s)")
    return {"op": f"paged_gather_r{r}_h{hkv}_s{maxp*ps}_d{d}_{k.dtype.name}",
            "xla_us": round(t * 1e6, 1),
            "xla_gbs": round(nbytes / t / 1e9, 1)}


def bench_ragged_attn(r, hq, hkv, maxp, ps, d, width, dtype=jnp.bfloat16,
                      iters=50):
    """The superkernel tick's attention shape: a MIXED batch where half
    the rows are decode rows (chunk_len 1) and half are ragged prefill
    chunks (chunk_len up to ``width``), all against the paged pool in one
    program (ops/pallas/ragged_paged_attention.py) vs the gather-then-
    dense XLA fallback."""
    rng = np.random.default_rng(2)
    cache, k, v = _paged_fixture(r, hkv, maxp, ps, d, dtype)
    q = jnp.asarray(rng.standard_normal((r, width, hq, d)), jnp.bfloat16)
    # even rows decode at full history; odd rows prefill a ragged chunk
    chunk = np.where(np.arange(r) % 2 == 0, 1,
                     1 + np.arange(r) % width).astype(np.int32)
    kv_len = np.where(chunk == 1, maxp * ps,
                      maxp * ps - width + chunk).astype(np.int32)
    chunk, kv_len = jnp.asarray(chunk), jnp.asarray(kv_len)
    nbytes = 2 * r * maxp * ps * hkv * d * k.dtype.itemsize

    f_kern = jax.jit(lambda q, k, v: ragged_paged_sdpa(
        q, k, v, cache.tables, kv_len, chunk))

    def ref(q, k, v):
        kd = cache.gather_layer(k).astype(jnp.bfloat16).transpose(0, 2, 1, 3)
        vd = cache.gather_layer(v).astype(jnp.bfloat16).transpose(0, 2, 1, 3)
        qpos = kv_len[:, None] - chunk[:, None] + jnp.arange(width)[None, :]
        return sdpa_reference(q, kd, vd, causal=True, q_positions=qpos,
                              kv_len=kv_len)
    f_ref = jax.jit(ref)
    tk = timeit(f_kern, q, k, v, iters=iters)
    tr = timeit(f_ref, q, k, v, iters=iters)
    print(f"ragged_attn R={r} Hq={hq} Hkv={hkv} S={maxp*ps} W={width} "
          f"D={d} {k.dtype}: kernel {tk*1e6:8.1f}us "
          f"({nbytes/tk/1e9:6.1f} GB/s) | xla {tr*1e6:8.1f}us "
          f"({nbytes/tr/1e9:6.1f} GB/s)")
    return {"op": (f"ragged_attn_r{r}_h{hq}/{hkv}_s{maxp*ps}_w{width}"
                   f"_d{d}_{k.dtype.name}"),
            "pallas_us": round(tk * 1e6, 1), "xla_us": round(tr * 1e6, 1),
            "pallas_gbs": round(nbytes / tk / 1e9, 1),
            "xla_gbs": round(nbytes / tr / 1e9, 1)}


def bench_ragged_walk(kv_len, width, dead=0, r=16, hq=32, hkv=8, ps=128,
                      d=128, layers=8, dtype=jnp.bfloat16, iters=50):
    """The decode stage's attention as a tick runs it: ``r`` rows of one
    query each, ``dead`` of them idle (``chunk_len`` 0 at the scratch
    sentinel), every live row ``kv_len`` slots long in a table ``width``
    pages wide, over a stack of ``layers`` read one layer after another.
    What the page walk must show on the chip: the time follows the live
    pages (``kv_len``), not the table's ``width`` and not the dead rows;
    the bytes are the live pages' K and V, against the peak HBM rate."""
    rng = np.random.default_rng(4)
    live_pages = -(-kv_len // ps)
    n_pages = 1 + r * live_pages
    tables = np.full((r, width), -1, np.int32)
    tables[:, :live_pages] = 1 + rng.permutation(
        r * live_pages).reshape(r, live_pages)
    shape = (layers, n_pages, hkv, ps, d)
    k = jnp.asarray(rng.standard_normal(shape, np.float32)).astype(dtype)
    v = jnp.asarray(rng.standard_normal(shape, np.float32)).astype(dtype)
    q = jnp.asarray(rng.standard_normal((r, 1, hq, d)), jnp.bfloat16)
    alive = np.arange(r) >= dead
    lens = jnp.asarray(np.where(alive, kv_len, width * ps), jnp.int32)
    chunk = jnp.asarray(alive, jnp.int32)
    tables = jnp.asarray(tables)
    nbytes = (2 * int(alive.sum()) * live_pages * ps * hkv * d
              * k.dtype.itemsize)

    @jax.jit
    def stack_walk(q, k, v):
        def one(acc, layer):
            return acc + ragged_paged_sdpa(q, k, v, tables, lens, chunk,
                                           layer=layer), None
        return jax.lax.scan(one, jnp.zeros_like(q), jnp.arange(layers))[0]

    t = timeit(stack_walk, q, k, v, iters=iters) / layers
    print(f"ragged_walk R={r} dead={dead} Hq={hq} Hkv={hkv} kv_len={kv_len} "
          f"table={width}x{ps} D={d} {k.dtype}: {t*1e6:8.1f}us a layer "
          f"({nbytes/t/1e9:6.1f} GB/s of live pages)")
    return {"op": (f"ragged_walk_r{r}_dead{dead}_h{hq}/{hkv}_len{kv_len}"
                   f"_w{width}_d{d}_{k.dtype.name}"),
            "pallas_us": round(t * 1e6, 1),
            "pallas_gbs": round(nbytes / t / 1e9, 1)}


def bench_spec_verify(r, hq, hkv, maxp, ps, d, k_spec, dtype=jnp.bfloat16,
                      iters=50):
    """The on-device speculative verify shape: ONE [R, k+1] ragged
    attention pass off the paged pool vs the k+1 sequential T=1 decode
    passes (the same kernel, one query a row) the same tokens would cost
    without speculation.  Decode is
    KV-bandwidth-bound, so the wide verify reads each row's pages once
    where the sequential chain reads them k+1 times — the roofline
    argument for the fused spec tick (a draft run that fully accepts
    emits k+1 tokens for ~one pool sweep)."""
    rng = np.random.default_rng(3)
    cache, k, v = _paged_fixture(r, hkv, maxp, ps, d, dtype)
    k1 = k_spec + 1
    q_wide = jnp.asarray(rng.standard_normal((r, k1, hq, d)), jnp.bfloat16)
    q_one = q_wide[:, :1]
    base = maxp * ps - k1
    kv_len = jnp.full((r,), maxp * ps, jnp.int32)
    chunk = jnp.full((r,), k1, jnp.int32)
    one = jnp.ones((r,), jnp.int32)
    # bytes the sequential chain re-reads: k+1 sweeps of every row's pool
    nbytes = 2 * r * maxp * ps * hkv * d * k.dtype.itemsize * k1

    f_wide = jax.jit(lambda q, k, v: ragged_paged_sdpa(
        q, k, v, cache.tables, kv_len, chunk))

    def chain(q, k, v):
        outs = []
        for j in range(k1):
            outs.append(ragged_paged_sdpa(
                q, k, v, cache.tables,
                jnp.full((r,), base + j + 1, jnp.int32), one))
        return jnp.concatenate(outs, axis=1)
    f_chain = jax.jit(chain)
    tw = timeit(f_wide, q_wide, k, v, iters=iters)
    tc = timeit(f_chain, q_one, k, v, iters=iters)
    print(f"spec_verify R={r} Hq={hq} Hkv={hkv} S={maxp*ps} k={k_spec} "
          f"D={d} {k.dtype}: wide {tw*1e6:8.1f}us "
          f"({nbytes/tw/1e9:6.1f} GB/s eff) | chain {tc*1e6:8.1f}us "
          f"({nbytes/tc/1e9:6.1f} GB/s)")
    return {"op": (f"spec_verify_r{r}_h{hq}/{hkv}_s{maxp*ps}_k{k_spec}"
                   f"_d{d}_{k.dtype.name}"),
            "pallas_us": round(tw * 1e6, 1), "xla_us": round(tc * 1e6, 1),
            "pallas_gbs": round(nbytes / tw / 1e9, 1),
            "xla_gbs": round(nbytes / tc / 1e9, 1)}


def bench_collectives(rows=8, hidden=4096, tp=4, iters=50):
    """Per-call cost of one decode-shaped AllReduce per wire family
    (ops/collectives.py: bf16-exact / e5m2 / int8).  The payload is the
    row-parallel combine the manual-tp tick pays twice per layer: [rows, hidden] f32
    partials reduced over the tp axis inside a fully-manual shard_map
    region.  On the CPU mesh the numbers price the family's code/decode
    arithmetic (the wire is emulated); on TPU they are the real ICI
    story."""
    from jax.sharding import PartitionSpec as P

    from ipex_llm_tpu.ops import collectives
    from ipex_llm_tpu.parallel import MeshSpec, make_mesh

    if tp > len(jax.devices()):
        print(f"collectives: skip tp={tp} (have {len(jax.devices())} "
              "devices)")
        return []
    mesh = make_mesh(MeshSpec(tp=tp))
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((rows, hidden)), jnp.float32)
    nbytes = rows * hidden * 4
    out = []
    for q in collectives.ALLREDUCE_QTYPES:
        fn = jax.jit(jax.shard_map(
            lambda v, q=q: collectives.all_reduce(v, "tp", qtype=q),
            mesh=mesh, in_specs=P(), out_specs=P(),
            axis_names={"tp"}, check_vma=False))
        t = timeit(fn, x, iters=iters)
        print(f"all_reduce[{q}] [{rows}x{hidden}] tp={tp}: "
              f"{t*1e6:8.1f}us ({nbytes/t/1e9:6.1f} GB/s payload)")
        out.append({"op": f"all_reduce_{q}_r{rows}x{hidden}_tp{tp}",
                    "us": round(t * 1e6, 1),
                    "gbs": round(nbytes / t / 1e9, 1)})
    return out


def collect(iters: int = 20) -> list[dict]:
    """Compact per-kernel summary (fail-soft: an op whose kernel path is
    ineligible on this backend is skipped).

    Off-TPU the kernels run in Pallas INTERPRET mode (dispatch falls back
    automatically): timings are then a correctness-execution record, not a
    bandwidth number — entries carry ``"interpret": true`` and GB/s fields
    are omitted so a CPU round still produces the per-kernel block
    (VERDICT r4 weak #8) without a fake roofline.
    """
    on_tpu = jax.default_backend() == "tpu"
    out = []
    if on_tpu:
        jobs = [
            (bench_qmatmul, (1, 4096, 12288), {"iters": iters}),  # merged qkv
            (bench_qmatmul, (1, 11008, 4096), {"iters": iters}),  # down
            (bench_qmatmul, (1, 4096, 32000), {"iters": iters}),  # lm head
            # small-row decode shapes (M = concurrent decode rows in the
            # fused tick)
            (bench_qmatmul, (8, 4096, 12288), {"iters": iters}),
            (bench_qmatmul, (8, 11008, 4096), {"iters": iters}),
            (bench_decode_attn, (1, 32, 32, 1280, 128), {"iters": iters}),
            (bench_decode_attn, (1, 32, 8, 4096, 128),
             {"dtype": jnp.float8_e5m2, "iters": iters}),         # fp8 KV
            # paged serving pool: 16 rows x 16 pages of 128 slots
            (bench_paged_gather, (16, 8, 16, 128, 128), {"iters": iters}),
            (bench_paged_gather, (16, 8, 16, 128, 128),
             {"dtype": jnp.float8_e5m2, "iters": iters}),
            # superkernel tick shape: mixed decode + ragged prefill rows
            (bench_ragged_attn, (16, 32, 8, 16, 128, 128, 32),
             {"iters": iters}),
            (bench_ragged_attn, (16, 32, 8, 16, 128, 128, 32),
             {"dtype": jnp.float8_e5m2, "iters": iters}),
            # the tick's decode stage: short rows in a full-width table
            (bench_ragged_walk, (512, 32), {"iters": iters}),
            # speculative verify: one [R, k+1] pass vs k+1 decode passes
            (bench_spec_verify, (16, 32, 8, 16, 128, 128, 4),
             {"iters": iters}),
            (bench_spec_verify, (16, 32, 8, 16, 128, 128, 4),
             {"dtype": jnp.float8_e5m2, "iters": iters}),
        ]
    else:
        # interpret-mode shapes: small enough that the Pallas interpreter
        # (orders of magnitude slower than compiled) finishes in seconds
        jobs = [
            # decode-shape qmatmul rows M=1..8 (interpret vs XLA)
            (bench_qmatmul, (1, 256, 512), {"iters": 2}),
            (bench_qmatmul, (2, 256, 512), {"iters": 2}),
            (bench_qmatmul, (4, 256, 512), {"iters": 2}),
            (bench_qmatmul, (8, 256, 512), {"iters": 2}),
            (bench_decode_attn, (1, 8, 4, 256, 64), {"iters": 2}),
            (bench_decode_attn, (1, 8, 4, 256, 64),
             {"dtype": jnp.float8_e5m2, "iters": 2}),
            (bench_paged_gather, (2, 4, 4, 32, 64), {"iters": 2}),
            (bench_paged_gather, (2, 4, 4, 32, 64),
             {"dtype": jnp.float8_e5m2, "iters": 2}),
            # superkernel tick shape (interpret record)
            (bench_ragged_attn, (2, 8, 4, 4, 32, 64, 8), {"iters": 2}),
            (bench_ragged_attn, (2, 8, 4, 4, 32, 64, 8),
             {"dtype": jnp.float8_e5m2, "iters": 2}),
            (bench_ragged_walk, (40, 4),
             {"dead": 1, "r": 2, "hq": 8, "hkv": 4, "ps": 32, "d": 64,
              "layers": 2, "iters": 2}),
            # speculative verify (interpret record)
            (bench_spec_verify, (2, 8, 4, 4, 32, 64, 3), {"iters": 2}),
        ]
    for fn, args, kw in jobs:
        try:
            row = fn(*args, **kw)
            if not on_tpu:
                row["interpret"] = True
                row.pop("pallas_gbs", None)
                row.pop("xla_gbs", None)
            out.append(row)
        except Exception as e:  # noqa: BLE001 — record, keep benching
            print(f"microbench skip {fn.__name__}{args}: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
    # collective wire families (the manual-tp AllReduce): the
    # decode-shaped payload on TPU, a smaller one for the CPU-mesh record
    try:
        shape = (8, 4096, 4) if on_tpu else (8, 1024, 4)
        out.extend(bench_collectives(*shape,
                                     iters=iters if on_tpu else 5))
    except Exception as e:  # noqa: BLE001
        print(f"microbench skip bench_collectives: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
    return out


if __name__ == "__main__":
    print("backend:", jax.default_backend())
    # llama-7B decode shapes
    bench_qmatmul(1, 4096, 12288)   # merged qkv
    bench_qmatmul(1, 4096, 4096)    # o
    bench_qmatmul(1, 4096, 22016)   # merged gate_up
    bench_qmatmul(1, 11008, 4096)   # down
    bench_qmatmul(1, 4096, 32000)   # lm head
    bench_qmatmul(16, 4096, 22016)  # small-batch serving shape
    bench_decode_attn(1, 32, 32, 1280, 128)
    bench_decode_attn(1, 32, 8, 4096, 128)                 # GQA long
    bench_decode_attn(1, 32, 8, 4096, 128, jnp.float8_e5m2)  # fp8 KV
    # paged serving pool (16 rows x 16 pages x 128 slots), bf16 vs fp8
    bench_paged_gather(16, 8, 16, 128, 128)
    bench_paged_gather(16, 8, 16, 128, 128, jnp.float8_e5m2)
    # ragged superkernel batch (mixed decode + prefill rows), bf16 vs fp8
    bench_ragged_attn(16, 32, 8, 16, 128, 128, 32)
    bench_ragged_attn(16, 32, 8, 16, 128, 128, 32, jnp.float8_e5m2)
    # the page walk at Mistral's decode shapes: the same rows in a table of
    # 8 and of 32 pages, cache lengths (the long-cache stand-in), dead rows
    for kv_len, width, dead in ((512, 8, 0), (512, 32, 0), (1024, 8, 0),
                                (1024, 32, 0), (4096, 32, 0), (512, 32, 10),
                                (4096, 32, 10)):
        bench_ragged_walk(kv_len, width, dead)
    # speculative verify: one [R, k+1] pass vs the k+1-step decode chain
    bench_spec_verify(16, 32, 8, 16, 128, 128, 4)
    bench_spec_verify(16, 32, 8, 16, 128, 128, 4, jnp.float8_e5m2)
    # collective wire families (manual-tp row-parallel combine shape)
    bench_collectives(8, 4096, 4)
