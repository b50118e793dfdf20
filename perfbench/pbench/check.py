"""The outputs check: served greedy tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and holding the request with
the most served tokens, is run through the reference once, prompt and
served tokens together. At each served position the gap by which the
served token's reference logit lies below the reference's best is read;
the widest gap over all positions is the number compared.

The fp8 control runs the same reference with every matmul operand
rounded to float8_e4m3fn and reads, at the same positions, the gap of the
token it puts first. It is run by the calibration mode and the tests,
never by a measured run.

Before that, a probe of the served weights (the first elements of every
matrix of every layer, and of the embedding and head), taken while the
program still held them, is compared bit for bit with the weights the
reference rebuilds: the program serves the benchmark's weights and no
others.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from pbench import reference, weights

PROBE = 64                 # elements probed per matrix and layer
GROUP = 4                  # sequences per reference pass


@dataclass
class Served:
    prompt: List[int]
    tokens: List[int]


def sample(finished: Sequence[Served], n: int, seed: int) -> List[Served]:
    """``n`` finished requests drawn from the seed, with the one that has
    the most served tokens (then the longest context) among them."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: (len(finished[i].tokens),
                                  len(finished[i].prompt)))
    longest = order[-1]
    rest = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng(seed)
    picked = [longest] + [rest[i] for i in
                          rng.permutation(len(rest))[:max(0, n - 1)]]
    return [finished[i] for i in picked]


def _flat_head(x, n: int = PROBE) -> np.ndarray:
    return np.asarray(jnp.ravel(x)[:n].astype(jnp.float32))


def probe(params) -> Dict[str, np.ndarray]:
    """First elements of each served matrix: per layer for the stacked
    ones. Gains are left out: the program stores them shifted by one."""
    out = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        if leaf.dtype == jnp.float32:
            continue
        if name.startswith("['layers']"):
            out[name] = np.stack([_flat_head(leaf[i])
                                  for i in range(leaf.shape[0])])
        else:
            out[name] = _flat_head(leaf)
    return out


def probe_mismatch(c: Dict[str, Any], seed: int, vocab_rows: int,
                   served_probe: Dict[str, np.ndarray]) -> int:
    """How many probed served elements differ from the rebuilt weights."""
    mine = probe(weights.top(c, seed, vocab_rows))
    rows: Dict[str, list] = {}
    for i in range(c["num_layers"]):
        for name, v in probe(weights.layer(c, seed, i)).items():
            rows.setdefault("['layers']" + name, []).append(v)
    mine.update({k: np.stack(v) for k, v in rows.items()})
    bad = 0
    for name, got in served_probe.items():
        want = mine.get(name)
        if want is None or want.shape != got.shape:
            bad += got.size
        else:
            bad += int((want != got).sum())
    missing = set(mine) - set(served_probe)
    return bad + sum(mine[k].size for k in missing)


def _group_arrays(group: Sequence[Served], seq_len: int):
    n = len(group)
    s_max = max(len(r.tokens) for r in group)
    toks = np.zeros((n, seq_len), np.int32)
    pos = np.zeros((n, s_max), np.int32)
    served = np.zeros((n, s_max), np.int32)
    valid = np.zeros((n, s_max), bool)
    for i, r in enumerate(group):
        seq = r.prompt + r.tokens[:-1]
        if len(seq) > seq_len:
            raise ValueError(f"sequence of {len(seq)} tokens exceeds the "
                             f"reference length {seq_len}")
        toks[i, :len(seq)] = seq
        S = len(r.tokens)
        pos[i, :S] = np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + S)
        served[i, :S] = r.tokens
        valid[i, :S] = True
    return toks, pos, served, valid


def gaps(c: Dict[str, Any], seed: int, vocab_rows: int,
         seqs: Sequence[Served], seq_len: int, *,
         control: bool = False) -> Dict[str, Optional[float]]:
    """Widest logit gap of the served tokens (``served``), and with
    ``control`` of the fp8 control's first choices (``control``), over
    every served position of ``seqs``."""
    kw = dict(eps=float(c["norm_eps"]), theta=float(c["rope_theta"]),
              qk_norm=bool(c.get("qk_norm")))
    V, tied = int(c["vocab_size"]), bool(c.get("tie_embeddings"))
    worst = {"served": 0.0, "control": 0.0 if control else None}
    positions = 0
    top = weights.top(c, seed, vocab_rows)
    for g in range(0, len(seqs), GROUP):
        group = seqs[g:g + GROUP]
        toks, pos, served, valid = _group_arrays(group, seq_len)
        h = reference.embed(top, jnp.asarray(toks), vocab=V)
        hc = h
        for i in range(c["num_layers"]):
            w = weights.layer(c, seed, i)
            h = reference.layer(w, h, q="f32", **kw)
            if control:
                hc = reference.layer(w, hc, q="fp8", **kw)
            del w
        idx = jnp.asarray(pos)[..., None]
        lg = reference.logits(top, jnp.take_along_axis(h, idx, axis=1),
                              eps=kw["eps"], vocab=V, tied=tied, q="f32")
        best = lg.max(-1)
        at = jnp.take_along_axis(lg, jnp.asarray(served)[..., None],
                                 axis=-1)[..., 0]
        gap = np.where(valid, np.asarray(best - at), 0.0)
        worst["served"] = max(worst["served"], float(gap.max()))
        if control:
            lc = reference.logits(top, jnp.take_along_axis(hc, idx, axis=1),
                                  eps=kw["eps"], vocab=V, tied=tied, q="fp8")
            first = jnp.argmax(lc, axis=-1)
            at_c = jnp.take_along_axis(lg, first[..., None], axis=-1)[..., 0]
            gap_c = np.where(valid, np.asarray(best - at_c), 0.0)
            worst["control"] = max(worst["control"], float(gap_c.max()))
        positions += int(valid.sum())
    worst["positions"] = positions
    return worst
