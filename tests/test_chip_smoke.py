"""chip_smoke.py's phases at a tiny size on the CPU, its refusal to run
without a TPU, and the compile-cache placement rule it reports on.

The script's ``main()`` has no CPU mode; these tests import its phase
functions instead, so the code that must pass on the chip is the code that
runs here (server up over HTTP, wave answered, health assertions, every
kernel family against its reference in interpret mode).
"""

import copy
import os
import re
import subprocess
import sys

import pytest

import chip_smoke
import ipex_llm_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(chip_smoke.MISTRAL_7B_V03, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, vocab_size=128,
            max_position_embeddings=512)
TINY_ENGINE = {"max_rows": 4, "max_seq_len": 128, "page_size": 16}


@pytest.fixture(scope="module")
def tiny_model():
    chip_smoke.build_native_quantizer()
    return chip_smoke.build_model(TINY)


def test_kernels_match_references_in_interpret_mode(tiny_model):
    errs = chip_smoke.check_kernels(tiny_model.config, tiny_model.params,
                                    TINY_ENGINE, chunk=16, flash_len=128)
    families = {name.split(".")[0] for name in errs}
    assert families == {"qmatmul_sym_int4", "ragged_paged_sdpa",
                        "paged_kv_write", "flash_sdpa", "decode_sdpa"}
    # both pool widths of the pool's two kernels, decode and chunk widths,
    # and the attention's short rows in a wide table among dead rows
    assert {n for n in errs if n.startswith(("ragged", "paged"))} == {
        f"{k}.{s}.c{c}" for k in ("ragged_paged_sdpa", "paged_kv_write")
        for s in ("bf16", "fp8") for c in (1, 16)} | {
        f"ragged_paged_sdpa.{s}.short_wide" for s in ("bf16", "fp8")}


def test_server_answers_the_wave_over_http(tiny_model):
    plan = chip_smoke.traffic(tiny_model.config.vocab_size,
                              lens=(20, 40, 30, 50, 90, 70),
                              max_tokens=(6, 8, 7, 9, 5, 8))
    out, eng = chip_smoke.serve(tiny_model, TINY_ENGINE, plan)
    served = chip_smoke.check_served(plan, out, eng)
    assert served["requests"] == 8
    assert [len(t) for t in served["tokens"]] == [6, 8, 6, 8, 7, 9, 5, 8]
    assert served["health"]["status"] == "ok"
    assert served["health"]["prefix_hits"] >= 1

    # the engine's fault isolation must not fool the smoke: a request that
    # came back short, or as an error object while the server stayed up,
    # fails it
    short = copy.deepcopy(out)
    short["wave"][3]["tokens"].pop()
    with pytest.raises(chip_smoke.SmokeFailure, match="asked for"):
        chip_smoke.check_served(plan, short, eng)
    failed = copy.deepcopy(out)
    failed["single"]["error"] = {"error": {"code": "error"}}
    with pytest.raises(chip_smoke.SmokeFailure, match="engine recorded"):
        chip_smoke.check_served(plan, failed, eng)
    differs = copy.deepcopy(out)
    differs["wave"][1]["tokens"][-1] ^= 1
    with pytest.raises(chip_smoke.SmokeFailure, match="different tokens"):
        chip_smoke.check_served(plan, differs, eng)
    retried = copy.deepcopy(out)
    retried["health"]["fault_domain"]["retries"] = 1
    with pytest.raises(chip_smoke.SmokeFailure, match="retried or isolated"):
        chip_smoke.check_served(plan, retried, eng)

    place = chip_smoke.placement((eng.cache.k, eng.cache.v), "cpu")
    assert list(place["bytes_per_device"]) == [0]
    with pytest.raises(chip_smoke.SmokeFailure, match="not on a tpu"):
        chip_smoke.placement(eng.params, "tpu")
    # the engine is stopped, its state still lowers: on the CPU the tick
    # holds no Mosaic call (interpret mode) and no cache is configured
    tick = chip_smoke.check_compiled_tick(eng, expect_mosaic=False)
    assert tick["interpret"] and tick["tpu_custom_calls_in_hlo"] == 0
    assert tick["compile_cache_dir"] is None
    with pytest.raises(chip_smoke.SmokeFailure, match="interpret mode"):
        chip_smoke.check_compiled_tick(eng, expect_mosaic=True)


@pytest.mark.slow
def test_manual_tp_tick_with_the_pallas_kernels(monkeypatch):
    """What the first four-chip run found: inside the manual tp region the
    Pallas qmatmul sized its output from the weight's global shape.  The
    CPU suite never saw it, because off-TPU the region takes the XLA path;
    forcing the kernels (interpret mode) over 4 virtual devices does."""
    from ipex_llm_tpu.ops import dispatch

    monkeypatch.setenv("IPEX_LLM_TPU_FORCE_PALLAS", "1")
    dispatch.clear_cache()
    try:
        model = chip_smoke.build_model(dict(
            TINY, hidden_size=128, intermediate_size=256,
            num_attention_heads=8, num_key_value_heads=4))
        plan = chip_smoke.traffic(model.config.vocab_size,
                                  lens=(20, 40, 30, 50, 90, 70),
                                  max_tokens=(6, 8, 7, 9, 5, 8))
        out, eng = chip_smoke.serve(model, TINY_ENGINE, plan,
                                    tensor_parallel_size=4)
        served = chip_smoke.check_served(plan, out, eng,
                                         tensor_parallel_size=4)
    finally:
        dispatch.clear_cache()
    assert served["health"]["parallel"]["tp_manual"] is True
    assert served["health"]["compiles_warm"] == 0
    chip_smoke.check_spread(
        chip_smoke.placement((eng.cache.k, eng.cache.v), "cpu"), 4, "pool")
    # (at this size the replicated embedding outweighs check_spread's slack)
    weights = chip_smoke.placement(eng.params, "cpu")
    assert len(weights["bytes_per_device"]) == 4
    assert max(weights["bytes_per_device"].values()) \
        < weights["global_bytes"] / 2


def test_main_refuses_a_cpu_only_backend():
    """``python chip_smoke.py`` with no TPU: non-zero exit, says what it
    found, prints no result."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "platform='cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_main_ends_stdout_with_the_result_and_nothing_else(monkeypatch,
                                                          capsys):
    """The last stdout line is ``{"ok", "device"}`` with exactly those keys,
    after a pass and after a failed phase; the record goes on a line before
    it."""
    import json

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda: device)
    monkeypatch.setattr(chip_smoke, "run",
                        lambda *a: {"device": device, "tokens": [[1, 2]]})
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert json.loads(lines[0])["tokens"] == [[1, 2]]

    def failed_phase(*a):
        raise chip_smoke.SmokeFailure("request 0: HTTP 500")
    monkeypatch.setattr(chip_smoke, "run", failed_phase)
    assert chip_smoke.main() == 1
    out = capsys.readouterr()
    assert json.loads(out.out) == {"ok": False, "device": device}
    assert "request 0: HTTP 500" in out.err


def test_compile_cache_rule():
    """Placed from outside -> code sets nothing; held to the CPU -> none;
    otherwise one fixed gitignored path in the checkout."""
    rule = ipex_llm_tpu.compile_cache_dir
    assert rule({"JAX_COMPILATION_CACHE_DIR": "/somewhere"}) is None
    assert rule({"JAX_COMPILATION_CACHE_DIR": "/x", "JAX_PLATFORMS": ""}) \
        is None
    assert rule({"JAX_PLATFORMS": "cpu"}) is None
    fixed = rule({})
    assert fixed == rule({"JAX_PLATFORMS": "tpu"}) \
        == os.path.join(REPO, ".jax_compile_cache")
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as f:
        assert ".jax_compile_cache/" in f.read().split()
    # nothing that moves from run to run may be part of the path, and no
    # other spelling of the variable or directory survives in the package
    with open(ipex_llm_tpu.__file__, encoding="utf-8") as f:
        src = f.read()
    assert not re.search(r"tempfile|getpid|time\.|uuid|expanduser", src)
    assert "IPEX_LLM_TPU_COMPILE_CACHE" not in src


def test_compile_cache_env_is_left_to_jax():
    """With the variable set, importing the package writes no cache
    directory into jax's config: jax reads the variable itself."""
    code = (
        "import jax\n"
        "calls = []\n"
        "real = jax.config.update\n"
        "jax.config.update = lambda k, v: (calls.append(k), real(k, v))\n"
        "import ipex_llm_tpu\n"
        "assert 'jax_compilation_cache_dir' not in calls, calls\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    for env, want in (({"JAX_COMPILATION_CACHE_DIR": "/tmp/placed"},
                       "/tmp/placed"),
                      ({"JAX_PLATFORMS": "cpu"}, "None")):
        clean = {k: v for k, v in os.environ.items()
                 if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS")}
        proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                              env={**clean, **env}, capture_output=True,
                              text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.strip() == want
