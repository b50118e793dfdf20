"""The system under test: one configuration served by an in-process
``MAXServer``, deployed through ``POST /v2/model/{id}/deploy`` like any
asset and driven over HTTP from this process (the one that holds the chip).

The configuration is registered at run time as a new asset of the
exchange, built by the program's own text-generation wrapper at full
width (``smoke=False``). The only thing the benchmark puts in is the
weights: the wrapper's model ``init`` is the benchmark's generator
(``weights.program_params``), so the program builds its engine around
weights it did not make.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import threading
import urllib.error
import urllib.request
from functools import partial
from typing import Any, Dict

from pbench import traffic as traffic_mod
from pbench import weights

MODEL_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "vocab_size", "qk_norm", "rope_theta",
              "norm_eps", "tie_embeddings")


class SetupError(RuntimeError):
    """The deployment cannot serve the cell's traffic as stated."""


def http(base: str, method: str, path: str, body=None, timeout: float = 600):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(base + path, data,
                                 {"Content-Type": "application/json"},
                                 method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


class CompileCounter:
    """Backend compiles (persistent-cache loads included), through JAX's
    monitoring events; servers compile on their own threads."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1
                self.seconds += secs


def model_config(c: Dict[str, Any]):
    from repro.configs.base import ModelConfig
    if c["weights_dtype"] != "bfloat16":
        raise SetupError("the program serves full width in bfloat16 only; "
                         f"the configuration states {c['weights_dtype']}")
    return ModelConfig(name=c["name"], family="dense", source=c["source"],
                       **{k: c[k] for k in MODEL_KEYS})


class Deployment:
    """``start`` registers, builds, deploys; ``stop`` undeploys and frees
    every device buffer the deployment held."""

    def __init__(self, config: Dict[str, Any], seed: int, max_queue: int):
        self.c = config
        self.seed = seed
        self.max_queue = max_queue
        self.server = None
        self.base = ""
        self.cfg = model_config(config)

    # -- build --------------------------------------------------------------

    def _build(self, asset, **kw):
        from repro.core import assets
        import jax
        real = assets.build_model
        rows = self.cfg.padded_vocab_size

        def make(cfg, **mkw):
            # the wrapper calls jit(init)(PRNGKey(seed)) with the build
            # seed, which start() sets to seed31(--seed): its key is then
            # weights.seed_key(--seed), passed as an argument
            model = real(cfg, **mkw)
            init = partial(weights.program_params, self.c, vocab_rows=rows)
            key = jax.random.PRNGKey(0)
            want = jax.eval_shape(model.init, key)
            have = jax.eval_shape(init, key)
            if jax.tree.structure(want) != jax.tree.structure(have) or any(
                    (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                    zip(jax.tree.leaves(want), jax.tree.leaves(have))):
                raise SetupError("the program's parameter layout is not the "
                                 "one the benchmark's weights are made in")
            return model._replace(init=init)

        assets.build_model = make
        try:
            return assets.TextGenerationWrapper(asset, **kw)
        finally:
            assets.build_model = real

    def start(self) -> str:
        from repro.core import MAXServer, assets
        from repro.core.registry import EXCHANGE
        asset = dataclasses.replace(assets._make_asset(self.cfg),
                                    builder=self._build)
        EXCHANGE.register(asset, overwrite=True)
        s = self.c["serve"]
        self.server = MAXServer(auto_deploy=False, build_kw={
            "smoke": False, "seed": weights.seed31(self.seed),
            "max_batch": s["max_batch"],
            "max_seq": s["max_seq"], "decode_chunk": s["decode_chunk"]})
        self.server.__enter__()
        self.base = self.server.url
        body = {"service": "batched", "qos": {"max_queue": self.max_queue}}
        if s.get("paged"):
            body.update(paged=True, page_size=s["page_size"],
                        kv_pool_blocks=s["kv_pool_blocks"])
        code, env = http(self.base, "POST",
                         f"/v2/model/{self.cfg.name}/deploy", body)
        if code != 200 or env.get("status") != "ok":
            raise SetupError(f"deploy failed: {code} {env}")
        return self.base

    @property
    def service(self):
        return self.server.manager.get(self.cfg.name).service

    @property
    def engine(self):
        return self.service.engine

    def stop(self):
        if self.server is None:
            return
        try:
            http(self.base, "DELETE", f"/v2/model/{self.cfg.name}")
        finally:
            self.server.__exit__(None, None, None)
            self.server = None
            gc.collect()      # engines hold reference cycles through jits


def check_admits(eng, traffic: Dict[str, Any], outstanding: int,
                 max_queue: int):
    """Stop unless every request of the mix is admissible as sent by the
    engine ``eng``: prompt within ``max_prompt_len``, prompt plus output
    within ``max_seq``, a pool that holds every slot at that length, a
    queue above the most requests that can be outstanding."""
    _, p_hi = traffic_mod.length_range(traffic["prompt_tokens"])
    _, n_hi = traffic_mod.length_range(traffic["max_new_tokens"])
    problems = []
    if p_hi > eng.max_prompt_len() or not eng.fits_prompt(p_hi):
        problems.append(f"prompt of {p_hi} tokens is past "
                        f"max_prompt_len {eng.max_prompt_len()}")
    if p_hi + n_hi > eng.max_seq:
        problems.append(f"{p_hi} + {n_hi} tokens exceed max_seq "
                        f"{eng.max_seq}")
    if eng.paged:
        per_slot = math.ceil((p_hi + n_hi) / eng.page_size) + 1
        if eng.kv_pool_blocks < eng.max_batch * per_slot:
            problems.append(f"pool of {eng.kv_pool_blocks} pages holds "
                            f"fewer than {eng.max_batch} slots of "
                            f"{per_slot} pages")
    if outstanding >= max_queue:
        problems.append(f"{outstanding} outstanding requests reach "
                        f"max_queue {max_queue}")
    if problems:
        raise SetupError("; ".join(problems))


def annotate(obj, attr: str, name: str):
    """Wrap ``obj.attr`` in a profiler span named ``name`` (traced runs)."""
    import jax
    fn = getattr(obj, attr)

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    setattr(obj, attr, wrapped)
