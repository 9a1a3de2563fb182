"""The ``attn_roofline`` reader (PR 29) on a small synthetic trace

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_attn_roofline.py -q

``testdata/attn_roofline_small.json`` is the parsed form (xplane_spans.py)
of three ticks whose ``engine/dispatch`` spans carry the ``attn_pages_live``
stat, with the attention kernel's operations inside two of them (``CUT``
there).  No jax import.  tests/test_perfbench_attn_roofline.py runs the same
cases in tier-1, and with them (this file's last lines) test_cells.py's.
"""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH), HERE]

import run  # noqa: E402
import shapes_attention  # noqa: E402
import xplane_spans as xs  # noqa: E402
from readers import attn_roofline  # noqa: E402

CONFIG = run.load_json(os.path.join(BENCH, "configs",
                                    "mistral-7b-v0.3.int4.json"))
PARAMS = run.load_json(os.path.join(BENCH, "layer_metrics",
                                    "attn_roofline.json"))["reader"]
PAGE = 2 * 8 * 128 * 128 * 2          # K and V: 8 kv heads x 128 slots x 128, bf16
LAYERS = 32


def small() -> dict:
    with open(os.path.join(BENCH, "testdata",
                           "attn_roofline_small.json")) as f:
        return json.load(f)


def ctx_for(parsed, monkeypatch, config=CONFIG) -> dict:
    monkeypatch.setattr(xs, "load", lambda ctx: parsed)
    return {"trace": {"modules": []}, "config": config,
            "hf_config": run.hf_config(config),
            "device": {"kind": "TPU v5 lite"},
            "peaks": run.load_json(os.path.join(BENCH, "peaks.json"))}


def test_the_shapes_come_from_the_configuration():
    hf = run.hf_config(CONFIG)
    assert shapes_attention.page_bytes(hf, CONFIG) == PAGE == 524288
    assert shapes_attention.attention_layers(hf) == LAYERS
    fp8 = dict(CONFIG, engine=dict(CONFIG["engine"], kv_storage="fp8"))
    assert shapes_attention.page_bytes(hf, fp8) == PAGE // 2
    hybrid = dict(hf, hybrid_override_pattern="MEM*EM*E")
    assert shapes_attention.attention_layers(hybrid) == 2


def test_the_share_is_least_time_over_kernel_time(monkeypatch):
    ctx = ctx_for(small(), monkeypatch)
    got = attn_roofline.read(PARAMS, ctx)
    # the steady tick's 72 pages and the mixed tick's 30; the third tick's
    # 80 are left out with its program (no attention operation inside)
    least = (72 + 30) * LAYERS * PAGE / 819e9
    spent = 2 * 0.0015 + 0.0005 + 0.0010
    assert got == pytest.approx(100.0 * least / spent)
    assert 0 < got < 100
    note = ctx["notes"]["attn_roofline"]
    assert (note["ticks"], note["pages_live"]) == (2, 102)
    assert note["pages_table"] == 512 + 520 + 512
    assert note["kernel_s"] == pytest.approx(spent)


@pytest.mark.parametrize("undercount", [1, 10, 72])
def test_an_undercount_only_lowers_the_share(monkeypatch, undercount):
    """The stat is a lower bound (a joiner is left out), so the share errs
    low: it cannot pass what the full count gives, nor 100 %."""
    full = attn_roofline.read(PARAMS, ctx_for(small(), monkeypatch))
    parsed = small()
    for s in parsed["spans"]:
        if s[0] == "dispatch" and s[3]["attn_pages_live"] == 72:
            s[3]["attn_pages_live"] -= undercount
    low = attn_roofline.read(PARAMS, ctx_for(parsed, monkeypatch))
    assert low < full < 100


@pytest.mark.parametrize("case", ["no_stat", "no_kernel", "no_spans",
                                  "no_trace"])
def test_nothing_to_read_gives_none_and_does_not_raise(monkeypatch, case):
    """The parent's program writes no ``attn_pages_live``; a model served
    by the XLA fallback leaves no kernel operation; a CPU run no trace."""
    parsed = small()
    if case == "no_stat":
        for s in parsed["spans"]:
            s[3].pop("attn_pages_live", None)
            s[3].pop("attn_pages_table", None)
    elif case == "no_kernel":
        parsed["ops"] = [o for o in parsed["ops"]
                         if "ragged_paged_attention" not in o[0]]
    elif case == "no_spans":
        parsed["spans"] = []
    else:
        parsed = None
    ctx = ctx_for(parsed, monkeypatch)
    assert attn_roofline.read(PARAMS, ctx) is None
    assert "attn_roofline" not in ctx.get("notes", {})


def test_the_metric_resolves_in_the_two_mistral_cells_only():
    root = os.path.dirname(BENCH)
    for cell, there in (("mistral7b.decode-saturated", True),
                        ("mistral7b.chat-near-knee", True),
                        ("nemotron3nano.decode-saturated", False)):
        metrics = {m["name"]: m
                   for m in run.resolve_cell(root, cell)["per_layer"]}
        assert ("attn_roofline" in metrics) is there, cell
        if there:
            m = metrics["attn_roofline"]
            assert (m["layer"], m["moves"], m["unit"]) == (
                "kernels", "itl_p95_ms", "%")
            assert m["reader"] == PARAMS


# The table of cells and the window's percentiles (test_cells.py, PR 33) ride
# into tier-1 with this file: a benchmark PR may add no file under tests/, and
# this is the file its wrapper there collects.  A wrapper of their own makes
# this line superfluous.
from test_cells import *  # noqa: E402,F401,F403
