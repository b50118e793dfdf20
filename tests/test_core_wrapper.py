"""MAX framework contract: wrapper hooks, standardized envelope, registry,
skeleton, deployments (the paper's Sections 2.2 and 3.2)."""

import pytest

import repro.core.assets  # noqa: F401 — populates EXCHANGE
from repro.configs import ASSIGNED, DEMOS
from repro.core import (
    EXCHANGE, DeploymentManager, MAXError, MAXModelWrapper, ModelMetadata,
    ModelRegistry, register_asset, skeleton_source,
)
from repro.core.registry import ModelAsset


class _EchoWrapper(MAXModelWrapper):
    MODEL_META_DATA = ModelMetadata(
        id="echo", name="Echo", description="test", type="Text Generation")

    def __init__(self, asset=None, **kw):
        self.calls = []

    def _pre_process(self, inp):
        self.calls.append("pre")
        if inp == "boom":
            raise MAXError("bad input")
        return inp

    def _predict(self, x):
        self.calls.append("predict")
        return x

    def _post_process(self, r):
        self.calls.append("post")
        return [r]


def test_wrapper_hook_chain():
    w = _EchoWrapper()
    out = w.predict("hi")
    assert out == ["hi"]
    assert w.calls == ["pre", "predict", "post"]


def test_envelope_ok_and_error():
    w = _EchoWrapper()
    env = w.predict_envelope("hi")
    assert env["status"] == "ok"
    assert env["predictions"] == ["hi"]
    assert "latency_ms" in env
    env = w.predict_envelope("boom")
    assert env["status"] == "error"
    assert "bad input" in env["error"]


def test_exchange_has_all_assigned_archs_plus_demos():
    assert len(EXCHANGE) >= 12
    for name in ASSIGNED:
        assert name in EXCHANGE
    for name in DEMOS:
        assert name in EXCHANGE


def test_registry_listing_and_filters():
    gen = EXCHANGE.list(type_filter="Text Generation")
    assert all(a.metadata.type == "Text Generation" for a in gen)
    moe = EXCHANGE.list(tag="moe")
    assert {a.metadata.id for a in moe} == {
        "qwen3-moe-235b-a22b", "phi3.5-moe-42b-a6.6b"}


def test_registry_no_silent_overwrite():
    reg = ModelRegistry()
    asset = ModelAsset(_EchoWrapper.MODEL_META_DATA,
                       EXCHANGE.get("qwen3-4b").config,
                       lambda a, **kw: _EchoWrapper())
    reg.register(asset)
    with pytest.raises(ValueError):
        reg.register(asset)
    reg.register(asset, overwrite=True)


def test_skeleton_flow():
    reg = ModelRegistry()
    register_asset("echo", _EchoWrapper, registry=reg)
    built = reg.get("echo").build()
    assert built.predict("x") == ["x"]
    src = skeleton_source("my-model")
    assert "MAXModelWrapper" in src and "my-model" in src
    assert "_pre_process" in src and "_predict" in src


def test_deployment_isolation_and_stats():
    reg = ModelRegistry()
    register_asset("echo", _EchoWrapper, registry=reg)
    mgr = DeploymentManager(reg)
    dep = mgr.deploy("echo", mesh_slice="pod0/rows0-7")
    env = mgr.predict("echo", "hello")
    assert env["status"] == "ok"
    mgr.predict("echo", "boom")
    health = mgr.health()["echo"]
    assert health["requests"] == 2 and health["errors"] == 1
    assert health["mesh_slice"] == "pod0/rows0-7"
    assert mgr.undeploy("echo")
    with pytest.raises(KeyError):
        mgr.get("echo")


def test_sentiment_envelope_matches_paper_fig3():
    """The paper's Fig. 3 JSON: predictions = [[{"positive": p,
    "negative": n}]] with p + n == 1."""
    dep = DeploymentManager().deploy("max-sentiment")
    env = dep.predict(["i loved this", "i hated this"])
    assert env["status"] == "ok"
    preds = env["predictions"]
    assert len(preds) == 2
    for row in preds:
        assert isinstance(row, list) and len(row) == 1
        d = row[0]
        assert set(d) == {"positive", "negative"}
        assert abs(d["positive"] + d["negative"] - 1.0) < 1e-5


@pytest.mark.parametrize("smoke,dtype", [(True, "float32"),
                                         (False, "bfloat16")])
def test_full_width_builds_bf16_weights(smoke, dtype):
    """Reduced builds keep f32 weights; the full-width build serves bf16
    (the demo config is small at either setting)."""
    w = EXCHANGE.get("max-sentiment").build(smoke=smoke, max_seq=32,
                                            max_batch=1)
    layers = w.params["layers"]
    for leaf in (w.params["embed"], layers["attn"]["wq"],
                 layers["mlp"]["w_up"]):
        assert str(leaf.dtype) == dtype
