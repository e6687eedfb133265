"""The one general traffic generator. A traffic mix is a data file under
``traffic/``; this module turns it and ``--seed`` into the work of a run.

Every seed gets the *same schedule* — sizes, gaps and their order, drawn
once from the file's own ``sizes_seed`` — and its own token ids (and, in
the cells, its own weights). So two seeds differ in what is said, never
in how much work there is or in what meets what: a tail over a hundred
requests swings by tens of percent when long prompts meet a burst in one
order and not in another, and the driver holds the spread *across* seeds
to a fifth of the bound."""

from __future__ import annotations

import json
from dataclasses import dataclass

import numpy as np


@dataclass
class Request:
    seq: int
    due_s: float            # open loop: when it is due; closed: 0
    prompt: np.ndarray      # (L,) int32
    max_new: int
    group: int              # shared-prefix or document id, -1 for none
    shared_tokens: int      # leading tokens it shares with its group


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _quantum(x, q: int):
    return np.maximum(q, (np.round(np.asarray(x) / q) * q)).astype(np.int64)


def draw_lengths(rng, spec: dict, n: int) -> np.ndarray:
    """``n`` lengths from a ``{"dist": ...}`` block, rounded to its
    ``quantum`` (so that a mix uses a bounded set of shapes)."""
    q = int(spec.get("quantum", 1))
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"], n)
    elif spec["dist"] == "fixed":
        x = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"traffic: unknown dist {spec['dist']!r}")
    x = np.clip(x, spec.get("min", 1), spec.get("max", np.inf))
    return _quantum(x, q)


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(1, vocab, n, dtype=np.int64).astype(np.int32)


def open_requests(mix: dict, seed: int, seconds: float, vocab: int
                  ) -> list[Request]:
    """Poisson arrivals at ``rate_rps`` for ``seconds``: n = rate × seconds
    requests whose gaps (exponential, scaled to fill the window) and
    sizes come from ``sizes_seed``; the seed draws the token ids."""
    n = max(1, int(round(mix["rate_rps"] * seconds)))
    fixed = np.random.default_rng(int(mix["sizes_seed"]))
    gaps = fixed.exponential(1.0, n)
    gaps *= seconds * (n / (n + 1.0)) / gaps.sum()
    suffix = draw_lengths(fixed, mix["suffix"], n)
    out = draw_lengths(fixed, mix["output"], n)
    sp = mix.get("shared_prefixes") or {"count": 0, "tokens": 0}
    groups = (np.arange(n) % sp["count"] if sp["count"] else
              np.full(n, -1))
    rng = np.random.default_rng(int(seed))
    due = np.cumsum(gaps)
    prefixes = [_tokens(rng, sp["tokens"], vocab)
                for _ in range(sp["count"])]
    reqs = []
    for i in range(n):
        g = int(groups[i])
        body = _tokens(rng, int(suffix[i]), vocab)
        prompt = np.concatenate([prefixes[g], body]) if g >= 0 else body
        reqs.append(Request(i, float(due[i]), prompt, int(out[i]), g,
                            sp["tokens"] if g >= 0 else 0))
    return reqs


def closed_requests(mix: dict, seed: int, seconds: float, vocab: int
                    ) -> list[Request]:
    """A replay list for a pool of ``clients``: rounds of ``documents``,
    each asked several times with a question of its own, the asks of a
    round shuffled. Long enough for ``max_rps × seconds`` requests; the
    window closes on whatever of it was reached."""
    docs = mix["documents"]
    fixed = np.random.default_rng(int(mix["sizes_seed"]))
    rng = np.random.default_rng(int(seed))
    want = int(np.ceil(mix["max_rps"] * seconds))
    reqs: list[Request] = []
    doc_id = 0
    while len(reqs) < want:
        lens = draw_lengths(fixed, docs["length"], docs["count"])
        asks = fixed.integers(docs["asks"][0], docs["asks"][1] + 1,
                              docs["count"])
        n = int(asks.sum())
        qlen = draw_lengths(fixed, mix["question"], n)
        out = draw_lengths(fixed, mix["output"], n)
        bodies = [_tokens(rng, int(L), vocab) for L in lens]
        which = np.repeat(np.arange(docs["count"]), asks)
        order = fixed.permutation(n)
        for j in order:
            d = int(which[j])
            q = _tokens(rng, int(qlen[j]), vocab)
            reqs.append(Request(len(reqs), 0.0,
                                np.concatenate([bodies[d], q]),
                                int(out[j]), doc_id + d, len(bodies[d])))
        doc_id += docs["count"]
    return reqs


def requests(mix: dict, seed: int, seconds: float, vocab: int
             ) -> list[Request]:
    if mix["kind"] == "open":
        return open_requests(mix, seed, seconds, vocab)
    if mix["kind"] == "closed":
        return closed_requests(mix, seed, seconds, vocab)
    raise ValueError(f"traffic: kind {mix['kind']!r} sends no requests")


def prompt_lengths(mix: dict, seconds: float) -> list[int]:
    """Every prompt length the mix can send in a window of ``seconds``
    (the same for all seeds) — what set-up has to warm."""
    return sorted({len(r.prompt)
                   for r in requests(mix, 0, seconds, vocab=8)})


def train_batch_fn(vocab: int, batch: int, seq: int, seed: int,
                   sharding=None):
    """step → {"tokens", "targets"} made on the device from the seed:
    fresh rows every step, all different, next-token targets."""
    import jax
    import jax.numpy as jnp

    from benchmark.weights import seed_key

    base = jax.random.fold_in(seed_key(seed), 0x7A11)

    def make(step):
        toks = jax.random.randint(jax.random.fold_in(base, step),
                                  (batch, seq + 1), 0, vocab, jnp.int32)
        return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    out = None if sharding is None else {"tokens": sharding,
                                         "targets": sharding}
    return jax.jit(make, out_shardings=out)
