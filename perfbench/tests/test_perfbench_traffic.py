"""The traffic generator: deterministic by seed, the same work for every
seed, and never a request the cell's deployment would refuse."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from pbench import spec, traffic  # noqa: E402
from pbench.serve import SetupError, check_admits  # noqa: E402

BIG_SEED = 2**31 + 977
BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _prompts(reqs):
    return [(r.prompt, r.max_new_tokens, r.due) for r in reqs]


@pytest.mark.parametrize("mix", ["batch", "docqa"])
def test_same_seed_same_requests(mix):
    t = _mix(mix)
    if t["loop"] == "open":
        a = traffic.Generator(t, BIG_SEED, bos=256).open(30)
        b = traffic.Generator(t, BIG_SEED, bos=256).open(30)
    else:
        ga = traffic.Generator(t, BIG_SEED, bos=256).closed()
        gb = traffic.Generator(t, BIG_SEED, bos=256).closed()
        a = [next(ga) for _ in range(40)]
        b = [next(gb) for _ in range(40)]
    assert _prompts(a) == _prompts(b)
    c = traffic.Generator(t, BIG_SEED + 1, bos=256)
    other = c.open(30) if t["loop"] == "open" else [next(c.closed())
                                                    for _ in range(40)]
    assert _prompts(other) != _prompts(a)


def test_every_seed_sends_the_same_work():
    """Every seed sends the same sizes at the same times (decks of
    stratified quantiles dealt in the mix's own order); only the prompt
    contents differ."""
    t = _mix("docqa")
    runs = [traffic.Generator(t, s, bos=256).open(200) for s in (1, 2, 3)]
    shape = [[(len(x.prompt), x.max_new_tokens, x.due) for x in r]
             for r in runs]
    assert shape[0] == shape[1] == shape[2]
    deck = int(t["deck"])
    first = sorted(len(x.prompt) for x in runs[0][:deck])
    assert first == traffic.quantiles(t["prompt_tokens"], deck)
    assert runs[0][0].prompt != runs[1][0].prompt
    c = _mix("batch")
    ga, gb = (traffic.Generator(c, s, bos=256).closed() for s in (1, 2))
    a = [next(ga) for _ in range(40)]
    b = [next(gb) for _ in range(40)]
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in b]


def test_quantiles_and_gaps():
    assert traffic.quantiles({"dist": "fixed", "value": 7}, 3) == [7, 7, 7]
    u = traffic.quantiles({"dist": "uniform", "min": 8, "max": 32}, 25)
    assert u == list(range(8, 33))
    lg = traffic.quantiles({"dist": "loguniform", "min": 256, "max": 1792},
                           16)
    assert lg == sorted(lg) and 256 <= lg[0] and lg[-1] <= 1792
    gaps = traffic.exp_gaps(2.0, 16)
    assert abs(gaps.mean() - 0.5) < 1e-12


def test_prompt_text_is_what_the_server_tokenizes():
    """The byte tokenizer puts BOS before the prompt's bytes."""
    from repro.data.tokenizer import TOKENIZER
    g = traffic.Generator(_mix("docqa"), 5, bos=TOKENIZER.bos_id)
    r = g.make(50, 4)
    assert len(r.prompt) == 50
    assert TOKENIZER.encode(r.text) == r.prompt


def test_warmup_reaches_every_bucket():
    t = {"prompt_tokens": {"dist": "loguniform", "min": 256, "max": 1792}}
    n = traffic.warmup_lengths(t)
    assert n == [256, 257, 512, 513, 1024, 1025, 1792]


def _engine(serve):
    """A small model behind an engine with the cell's serving sizes:
    admissibility depends on those sizes only."""
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    from repro.serving.engine import GenerationEngine
    cfg = ModelConfig(name="tiny", family="dense", num_layers=1, d_model=32,
                      num_heads=2, num_kv_heads=1, head_dim=16, d_ff=64,
                      vocab_size=512)
    model = build_model(cfg, param_dtype=jnp.bfloat16)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    kw = {}
    if serve.get("paged"):
        kw = dict(paged=True, page_size=serve["page_size"],
                  kv_pool_blocks=serve["kv_pool_blocks"])
    return GenerationEngine(model, params, max_batch=serve["max_batch"],
                            max_seq=serve["max_seq"],
                            decode_chunk=serve["decode_chunk"], **kw)


@pytest.mark.parametrize("name", CELLS)
def test_no_request_the_deployment_refuses(name):
    cell = spec.load_cell(name)
    s = cell.config["serve"]
    eng = _engine(s)
    check_admits(eng, cell.traffic, 2 * s["max_batch"], 4 * s["max_batch"])
    gen = traffic.Generator(cell.traffic, BIG_SEED, bos=s["bos_id"])
    reqs = gen.open(60) if cell.traffic["loop"] == "open" else \
        [next(gen.closed()) for _ in range(64)]
    for r in reqs:
        assert eng.fits_prompt(len(r.prompt))
        assert len(r.prompt) <= eng.max_prompt_len()
        assert len(r.prompt) + r.max_new_tokens <= eng.max_seq


def test_check_admits_refuses_what_does_not_fit():
    eng = _engine({"max_batch": 2, "max_seq": 256, "decode_chunk": 8})
    t = {"prompt_tokens": {"dist": "fixed", "value": 250},
         "max_new_tokens": {"dist": "fixed", "value": 16}}
    with pytest.raises(SetupError, match="exceed max_seq"):
        check_admits(eng, t, 1, 64)
    t["prompt_tokens"]["value"] = 16
    with pytest.raises(SetupError, match="max_queue"):
        check_admits(eng, t, 64, 64)
