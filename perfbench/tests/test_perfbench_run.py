"""Whole runs of a toy cell on the CPU, through the real harness with the
look for a chip skipped: the served path agrees with the reference, the
fp8 control does not, and a timed path broken underneath reads
``correct: false``."""

import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from pbench import spec, weights  # noqa: E402
from pbench.run_cell import run_cell  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"
SEED = 2**31 + 4321
# toy readings (CPU, bf16 program vs f32 reference, 70-80 served tokens):
# served 0.0-0.007, fp8 control 0.11-0.15
TOY_LIMITS = {"logit_gap": 0.05, "min_compared_tokens": 8}


def toy_cell(mix="toy_closed"):
    names = ("output_tok_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s")
    return spec.Cell(
        name=f"toy-gqa.{mix}", chips=1,
        config=json.loads((FIXTURES / "toy.json").read_text()),
        traffic=json.loads((FIXTURES / f"{mix}.json").read_text()),
        limits=dict(TOY_LIMITS),
        end_to_end=[{"name": n, "unit": "u"} for n in names],
        per_layer=[])


def run(cell, **kw):
    return run_cell(cell, SEED, 2.0, False, t_start=time.perf_counter(),
                    **kw)


def test_layer_by_layer_weights_are_the_served_ones():
    c = json.loads((FIXTURES / "toy.json").read_text())
    full = weights.program_params(c, weights.seed_key(SEED), 512)
    for i in range(c["num_layers"]):
        one = weights.layer(c, SEED, i)
        for path, leaf in jax.tree_util.tree_flatten_with_path(one)[0]:
            served = full["layers"]
            for k in path:
                served = served[k.key]
            served = np.asarray(served[i], np.float32)
            mine = np.asarray(leaf, np.float32)
            if leaf.dtype == np.float32:      # gains: stored as g - 1
                np.testing.assert_allclose(served + 1, mine, rtol=1e-6)
            else:
                np.testing.assert_array_equal(served, mine)
    top = weights.top(c, SEED, 512)
    np.testing.assert_array_equal(np.asarray(full["embed"], np.float32),
                                  np.asarray(top["embed"], np.float32))


def test_sound_run_is_correct_and_the_fp8_control_is_not():
    res = run(toy_cell(), calibrate=True)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) >= {"output_tok_s", "setup_s"}
    cal = res["calibration"]
    assert cal["served_gap"] == res["checks"]["logit_gap"]["value"]
    assert cal["control_correct"] is False, cal


def _alter_tokens(dep):
    eng = dep.engine
    real = eng.step_chunk

    def altered(*a, **kw):
        toks, emitted = real(*a, **kw)
        return (toks + 1) % eng.cfg.vocab_size, emitted
    eng.step_chunk = altered


def _skip_kv_insert(dep):
    eng = dep.engine
    eng._insert = lambda cache, *a: cache


@pytest.mark.parametrize("fault", [_alter_tokens, _skip_kv_insert],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(fault):
    res = run(toy_cell("toy_open"), break_path=fault)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > TOY_LIMITS["logit_gap"]
