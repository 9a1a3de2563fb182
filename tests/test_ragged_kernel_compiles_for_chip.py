"""The ragged attention kernel, and the recurrent state's, compiled by Mosaic
for a DESCRIBED v5e chip
(none attached), at the widths the benchmark's cells and the tp form bring:
what interpret mode cannot show (a copy not aligned to the tiling, more VMEM
than a kernel may plan) is refused here, at no chip time.  Nothing runs, so
this gives no result and no time.  Skipped where no TPU compiler can
describe the chip."""

import os

import jax
import jax.numpy as jnp
import pytest

# name: (rows, chunk, q heads, kv heads, head size, layers, table width, pool dtype)
SHAPES = {
    "mistral7b.decode": (16, 1, 32, 8, 128, 32, 32, jnp.bfloat16),
    "mistral7b.prefill128": (4, 128, 32, 8, 128, 32, 8, jnp.bfloat16),
    "mistral7b.decode.fp8": (16, 1, 32, 8, 128, 32, 32, jnp.float8_e5m2),
    "mistral7b.verify4": (16, 5, 32, 8, 128, 32, 32, jnp.bfloat16),
    "nemotron3nano.decode": (32, 1, 32, 2, 128, 6, 32, jnp.bfloat16),
    "mistral7b.tp4.decode": (16, 1, 8, 2, 128, 32, 32, jnp.bfloat16),
    # a GQA group of FIVE (20 query heads on 4): no power of two
    "falconh1.decode": (16, 1, 20, 4, 128, 24, 32, jnp.bfloat16),
    "falconh1.prefill512": (1, 512, 20, 4, 128, 24, 8, jnp.bfloat16),
    "falconh1.prefill32": (16, 32, 20, 4, 128, 24, 32, jnp.bfloat16),
}
# the state kernel: (layers, rows, tokens, heads, head size, groups, state)
STATE_SHAPES = {
    "nemotron3nano.step": (23, 32, 1, 64, 64, 8, 128),
    "falconh1.step": (24, 16, 1, 32, 128, 2, 256),
    "falconh1.chunk512": (1, 1, 512, 32, 128, 2, 256),
    "falconh1.chunk32": (1, 16, 32, 32, 128, 2, 256),
}
PAGE, POOL_PAGES = 128, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else it logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_mosaic_takes_the_page_walk(one_chip, name, monkeypatch):
    from ipex_llm_tpu.ops.pallas import ragged_paged_attention as rpa

    monkeypatch.setattr(rpa, "_interpret", lambda: False)
    rows, c, hq, hkv, d, layers, width, kv_dtype = SHAPES[name]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((layers, POOL_PAGES, hkv, PAGE, d), kv_dtype)
    compiled = jax.jit(
        lambda q, k, v, t, n, cl, layer: rpa.ragged_paged_sdpa(
            q, k, v, t, n, cl, layer=layer)).lower(
        arg((rows, c, hq, d), jnp.bfloat16), pool, pool,
        arg((rows, width), jnp.int32), arg((rows,), jnp.int32),
        arg((rows,), jnp.int32), arg((), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    # the pools are read where they lie: no pool-shaped temporary
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("name", sorted(STATE_SHAPES))
def test_mosaic_takes_the_state_step(one_chip, name, monkeypatch):
    """``ssm_scan`` at Nemotron's state (N = 128, two groups a step) and at
    Falcon-H1's (N = 256: two lane tiles a state row, HALF a group a step so
    that a step's state stays inside VMEM four times over)."""
    from ipex_llm_tpu.ops.pallas import ssm_scan

    monkeypatch.setattr(ssm_scan, "_interpret", lambda: False)
    layers, rows, t, heads, p, groups, n = STATE_SHAPES[name]

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stack = arg((layers, rows, heads, p, n))
    compiled = jax.jit(
        lambda src, dst, x, dt, a, b, c, lens: ssm_scan.scan_stack(
            src, dst, jnp.int32(0), x, dt, a, b, c, lens)).lower(
        stack, stack, arg((rows, t, heads, p)), arg((rows, t, heads)),
        arg((heads,)), arg((rows, t, groups, n)), arg((rows, t, groups, n)),
        arg((rows,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
