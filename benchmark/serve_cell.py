"""Serving cells (traffic ``kind: open`` or ``closed``): one
``PagedGeneratorActor`` on the chip, registered on a real ``ActorServer``,
behind ``InferenceGateway`` over the socket codec, in this process — the
way chip_smoke.py and the reconciler's LocalLauncher stand a replica up.

The load generator's threads only sleep or block on a socket. The
benchmark's own clock is read at the engine's seams (a request entering
``Generate``, a first token, a decode step's tokens) by taps installed
from here: the gateway returns whole answers, so the first token's time
exists nowhere else."""

from __future__ import annotations

import gc
import threading
import time
from unittest import mock

import numpy as np

from benchmark import family, harness, traffic


def prompt_key(prompt) -> tuple:
    a = np.ascontiguousarray(np.asarray(prompt, np.int32).reshape(-1))
    return (a.size, hash(a.tobytes()))


class ReqTap:
    """One request's stamps, all on this process's perf_counter."""

    __slots__ = ("req", "t_issue", "t_done", "t_enter", "t_exit", "rec",
                 "tok_t", "out", "err", "chunks")

    def __init__(self, req):
        self.req = req
        self.t_issue = self.t_done = self.t_enter = self.t_exit = None
        self.rec = None
        self.tok_t: list[float] = []
        self.out = None
        self.err = None
        self.chunks: list[tuple[float, int]] = []


class Taps:
    """The benchmark's clock at the engine's seams."""

    def __init__(self, actor):
        self.by_key: dict[tuple, ReqTap] = {}
        self.by_rec: dict[int, ReqTap] = {}
        self.steps: list[tuple[float, tuple]] = []
        self._tls = threading.local()
        led = actor.ledger
        gen, enq = actor.Generate, led.enqueued
        first, emitted, chunk = (led.first_token, led.tokens_emitted,
                                 led.chunk)
        now = time.perf_counter

        def Generate(prompt, *a, **kw):
            tap = self.by_key.get(prompt_key(prompt))
            self._tls.tap = tap
            if tap is not None:
                tap.t_enter = now()
            try:
                return gen(prompt, *a, **kw)
            finally:
                if tap is not None:
                    tap.t_exit = now()
                self._tls.tap = None

        def enqueued(*a, **kw):
            rec = enq(*a, **kw)
            tap = getattr(self._tls, "tap", None)
            if tap is not None:
                tap.rec = rec
                self.by_rec[id(rec)] = tap
            return rec

        def first_token(rec):
            t = now()
            first(rec)
            tap = self.by_rec.get(id(rec))
            if tap is not None:
                tap.tok_t.append(t)

        def tokens_emitted(recs, counts=None):
            t = now()
            emitted(recs, counts)
            live = []
            for i, rec in enumerate(recs):
                tap = self.by_rec.get(id(rec))
                if tap is not None:
                    n = 1 if counts is None else int(counts[i])
                    tap.tok_t.extend([t] * n)
                    live.append(tap)
            self.steps.append((t, tuple(live)))

        def chunk_meter(rec, tokens):
            tap = self.by_rec.get(id(rec))
            if tap is not None:
                tap.chunks.append((now(), int(tokens)))
            return chunk(rec, tokens)

        actor.Generate = Generate
        led.enqueued, led.first_token = enqueued, first_token
        led.tokens_emitted, led.chunk = tokens_emitted, chunk_meter

    def register(self, req) -> ReqTap:
        tap = ReqTap(req)
        self.by_key[prompt_key(req.prompt)] = tap
        return tap

    def reset(self) -> None:
        self.by_key.clear()
        self.by_rec.clear()
        self.steps.clear()


class Server:
    """The replica, its gateway and its taps; ``close`` frees them all."""

    def __init__(self, ctx):
        import jax

        from ptype_tpu import actor as actor_mod
        from ptype_tpu.gateway.frontdoor import (GatewayConfig,
                                                 InferenceGateway)
        from ptype_tpu.reconciler.replica import LocalLauncher
        from ptype_tpu.serve_engine.engine import PagedGeneratorActor

        mix, cfg = ctx["mix"], ctx["cfg"]
        eng = dict(mix["engine"])
        device = ctx["devices"][0]
        fam = family.of(cfg)
        tcfg = fam.program_config(cfg, eng["max_len"], cfg["param_dtype"])
        sharding = jax.sharding.SingleDeviceSharding(device)
        params = fam.tree(cfg, ctx["seed"], cfg["param_dtype"], sharding)
        self.taps = None

        def make(device=None):
            actor = PagedGeneratorActor(tcfg, params=params,
                                        device=device, **eng)
            self.taps = Taps(actor)
            return actor

        self.launcher = LocalLauncher(ctx["cluster"].registry, make,
                                      service="llm", devices=[device])
        self.launcher.spawn("bench-replica-0")
        self.actor = self.launcher.hosts[0].actor
        # In-process dials would skip the codec: force the socket.
        self._patch = mock.patch.object(actor_mod, "lookup_local",
                                        lambda addr, port: None)
        self._patch.start()
        self.gw = InferenceGateway(
            ctx["cluster"].registry, "llm",
            GatewayConfig(**mix.get("gateway", {})))
        deadline = time.monotonic() + 60
        while self.gw.pool.n_healthy() < 1:
            if time.monotonic() > deadline:
                raise SystemExit("benchmark: the replica never became "
                                 "healthy at the gateway")
            time.sleep(0.05)
        self.deadline_s = float(mix.get("deadline_s", 600.0))

    def ask(self, prompt: np.ndarray, max_new: int) -> np.ndarray:
        out = self.gw.generate(prompt[None].astype(np.int32), int(max_new),
                               deadline_s=self.deadline_s)
        return np.asarray(out)[0]

    def warm(self, ctx) -> None:
        """Every shape this cell's traffic uses, and no other: one
        prompt per prefill bucket (decoding three tokens, so the decode
        step runs on its own outputs), and the per-length eager ops of
        ``Generate``'s prompt normalisation for each prompt length the
        mix can send."""
        from ptype_tpu.serve import _norm_prompt

        vocab = int(ctx["cfg"]["vocab_size"])
        n, fill = 16, 1
        while n <= min(self.actor.prefill_chunk, self.actor.reach - 3):
            self.ask(np.full(n, fill % vocab, np.int32), 3)
            n, fill = n * 2, fill + 1
        for L in traffic.prompt_lengths(ctx["mix"], ctx["seconds"]):
            np.asarray(_norm_prompt(np.zeros((1, L), np.int32))[0])

    def close(self) -> None:
        self.gw.close()
        self._patch.stop()
        self.launcher.close()
        if self.taps is not None:
            self.taps.reset()
        self.actor = self.taps = self.gw = self.launcher = None


# ---------------------------------------------------------------- load


def drive(server: Server, mix: dict, reqs: list, seconds: float,
          trace_dir) -> dict:
    """Offer ``reqs`` (open loop: each at its due time; closed loop:
    ``clients`` workers taking the next as they finish) for ``seconds``,
    then wait for what is in flight."""
    taps = [server.taps.register(r) for r in reqs]
    closed = mix["kind"] == "closed"
    threads: list[threading.Thread] = []
    state = {"next": 0, "lock": threading.Lock()}

    def fire(tap: ReqTap):
        tap.t_issue = time.perf_counter()
        try:
            tap.out = server.ask(tap.req.prompt, tap.req.max_new)
        except Exception as e:  # noqa: BLE001 — counted as failed
            tap.err = repr(e)[:300]
        tap.t_done = time.perf_counter()

    with harness.traced_window(trace_dir):
        t_open = time.perf_counter()
        t_close = t_open + seconds
        if closed:
            def worker():
                while time.perf_counter() < t_close:
                    with state["lock"]:
                        i = state["next"]
                        state["next"] += 1
                    if i >= len(taps):
                        return
                    taps[i].req.due_s = time.perf_counter() - t_open
                    fire(taps[i])

            threads = [threading.Thread(target=worker, daemon=True)
                       for _ in range(int(mix["clients"]))]
            for th in threads:
                th.start()
            time.sleep(max(0.0, t_close - time.perf_counter()))
        else:
            for tap in taps:
                delay = t_open + tap.req.due_s - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                th = threading.Thread(target=fire, args=(tap,),
                                      daemon=True)
                th.start()
                threads.append(th)
            time.sleep(max(0.0, t_close - time.perf_counter()))
        backlog = sum(1 for t in taps if t.t_issue is not None
                      and t.t_done is None)
    drain_until = time.monotonic() + float(mix.get("drain_s", 120.0))
    for th in threads:
        th.join(timeout=max(0.0, drain_until - time.monotonic()))
    issued = [t for t in taps if t.t_issue is not None]
    return {"taps": issued, "t_open": t_open, "t_close": t_close,
            "backlog_at_close": backlog,
            "drain_s": time.perf_counter() - t_close}


def reduce(ctx, server: Server, drove: dict) -> dict:
    """From stamps to the cell's numbers."""
    cfg, mix = ctx["cfg"], ctx["mix"]
    taps, t_open, t_close = drove["taps"], drove["t_open"], drove["t_close"]
    seconds = t_close - t_open
    bt = int(mix["engine"]["block_tokens"])
    ok = [t for t in taps if t.err is None and t.out is not None
          and len(t.out) == t.req.max_new and len(t.tok_t) >= 1]
    failed = len(taps) - len(ok)
    e2e, counters = {}, {}
    ttft = [(t.tok_t[0] - (t_open + t.req.due_s)) * 1e3 for t in ok]
    gaps = [(b - a) * 1e3 for t in ok for a, b in zip(t.tok_t, t.tok_t[1:])]
    # A request that failed or never answered misses any limit: it sits
    # at the far end of every tail, at the deadline it was given.
    ttft_all = ttft + [server.deadline_s * 1e3] * failed
    if ttft_all:
        e2e["ttft_mean_ms"] = sum(ttft_all) / len(ttft_all)
        counters["ttft_p90_ms"] = harness.quantile(ttft_all, 0.90)
        counters["ttft_p50_ms"] = harness.quantile(ttft_all, 0.50)
    if gaps:
        e2e["itl_p95_ms"] = harness.quantile(gaps, 0.95)
        counters["itl_p50_ms"] = harness.quantile(gaps, 0.50)
    done_in = [t for t in ok if t.t_done <= t_close]
    # Tokens completed inside the window, whether or not their request
    # finished by the close: prompt tokens taken in (served from cached
    # blocks, counted when the request's first chunk starts, or
    # prefilled, chunk by chunk) and output tokens emitted. Whole
    # requests of ten thousand tokens would move the rate by a tenth
    # each as they fall on one side of the close or the other.
    inside = lambda x: t_open <= x <= t_close  # noqa: E731
    tokens_in = 0
    for t in ok:
        if t.rec is not None and t.chunks and inside(t.chunks[0][0]):
            tokens_in += t.rec.reused_blocks * bt
        tokens_in += sum(n for at, n in t.chunks if inside(at))
        tokens_in += sum(1 for at in t.tok_t if inside(at))
    e2e["serve_tok_s"] = tokens_in / seconds
    counters["serve_tok_s_whole_requests"] = sum(
        len(t.req.prompt) + t.req.max_new for t in done_in) / seconds
    counters["completed_in_window"] = len(done_in)
    counters["req_per_s_completed"] = len(done_in) / seconds
    lat = [(t.t_done - (t_open + t.req.due_s)) * 1e3 for t in ok]
    if lat:
        counters["req_e2e_p50_ms"] = harness.quantile(lat, 0.5)
    lag = [(t.t_issue - (t_open + t.req.due_s)) * 1e3 for t in taps]
    counters["issue_lag_p95_ms"] = harness.quantile(lag, 0.95)
    over = [(t.t_done - t.t_issue) - (t.t_exit - t.t_enter)
            for t in ok if t.t_exit is not None]
    if over:
        counters["gateway_ms_p50"] = harness.quantile(over, 0.5) * 1e3
    with_rec = [t for t in ok if t.rec is not None]
    ptoks = sum(len(t.req.prompt) for t in with_rec)
    if ptoks:
        counters["prefix_hit_pct"] = 100.0 * sum(
            t.rec.reused_blocks * bt for t in with_rec) / ptoks

    # What the window's iterations needed, from the family's own count
    # of what each saw: one context length a live row, and a chunk's
    # tokens each with the keys it attends to.
    fam = family.of(cfg)
    emitted: dict[int, int] = {}
    need_bytes, flops, decode_steps = 0.0, 0.0, 0
    prev_t = None
    iter_ms = []
    for t_step, live in server.taps.steps:
        ctxs, groups = [], {}
        for tap in live:
            k = id(tap)
            n = emitted.get(k, 1)  # the first token came from prefill
            ctxs.append(len(tap.req.prompt) + n)
            emitted[k] = n + 1
            if tap.req.group >= 0:
                groups[tap.req.group] = groups.get(tap.req.group, 0) + 1
        if t_open <= t_step <= t_close and live:
            shared = sum((c - 1) * (live[0].req.shared_tokens // bt) * bt
                         for c in groups.values() if c > 1)
            need_bytes += fam.decode_needed_bytes(cfg, ctxs, shared)
            flops += fam.forward_flops(cfg, len(live), ctxs)
            decode_steps += 1
            if prev_t is not None:
                iter_ms.append((t_step - prev_t) * 1e3)
        prev_t = t_step
    for tap in taps:
        if tap.rec is None:
            continue
        pos = tap.rec.reused_blocks * bt
        for t_chunk, n in tap.chunks:
            if t_open <= t_chunk <= t_close:
                flops += fam.forward_flops(
                    cfg, n, range(pos + 1, pos + n + 1))
            pos += n
    counters["decode_needed_bytes"] = need_bytes
    counters["model_flops_traced"] = flops
    counters["decode_steps"] = decode_steps
    if iter_ms:
        counters["engine_iter_ms_p50"] = harness.quantile(iter_ms, 0.5)
    counters["backlog_at_close"] = drove["backlog_at_close"]
    counters["drain_s"] = drove["drain_s"]
    counters["window_s"] = seconds
    return {"e2e": e2e, "counters": counters, "ok": ok, "failed": failed,
            "attempted": len(taps)}


# ------------------------------------------------------------- correct


def sample_for_check(ok: list, seed: int, k: int) -> list:
    """The longest finished request and k−1 more drawn from the seed."""
    if not ok:
        return []
    by_len = sorted(ok, key=lambda t: -(len(t.req.prompt) + t.req.max_new))
    rest = by_len[1:]
    rng = np.random.default_rng(seed)
    pick = rng.permutation(len(rest))[:max(0, k - 1)]
    return [by_len[0]] + [rest[i] for i in pick]


def served_gaps(cfg: dict, seed: int, sample: list, modes=("f32",),
                bucket: int = 512) -> dict:
    """For each served token, how far its logit lies below the
    reference's best at that position, worst case (``program``); and for
    each other mode, the same for the token that mode puts first."""
    import jax.numpy as jnp

    rows = [np.concatenate([np.asarray(p, np.int32), np.asarray(o, np.int32)])
            for p, o in sample]
    T = -(-max(len(r) for r in rows) // bucket) * bucket
    n = max(len(o) for _, o in sample)
    toks = np.zeros((len(rows), T), np.int32)
    idx = np.zeros((len(rows), n), np.int32)
    want = np.zeros((len(rows), n), np.int32)
    mask = np.zeros((len(rows), n), bool)
    for r, (p, o) in enumerate(sample):
        toks[r, :len(rows[r])] = rows[r]
        m = len(o)
        idx[r, :m] = len(p) - 1 + np.arange(m)
        idx[r, m:] = idx[r, m - 1]
        want[r, :m] = o
        mask[r, :m] = True
    logits = family.of(cfg).served_logits(
        cfg, seed, cfg["param_dtype"], jnp.asarray(toks), jnp.asarray(idx),
        modes=tuple(modes))
    ref = np.asarray(logits["f32"])
    best = ref.max(axis=-1)
    out = {"program": float(np.max(np.where(
        mask, best - np.take_along_axis(ref, want[..., None], -1)[..., 0],
        0.0))), "tokens": int(mask.sum())}
    for m in modes:
        if m == "f32":
            continue
        first = np.asarray(logits[m]).argmax(axis=-1)
        out[m] = float(np.max(np.where(
            mask, best - np.take_along_axis(ref, first[..., None],
                                            -1)[..., 0], 0.0)))
    return out


# ------------------------------------------------------------------ run


def run(ctx) -> dict:
    mix, cfg = ctx["mix"], ctx["cfg"]
    server = Server(ctx)
    try:
        server.warm(ctx)
        reqs = traffic.requests(mix, ctx["seed"], ctx["seconds"],
                                int(cfg["vocab_size"]))
        if ctx.get("fault") == "token_altered":
            ask = server.ask
            server.ask = lambda p, n: _altered(ask(p, n), cfg)
        compiles0 = ctx["compiles"].n
        setup_s = time.perf_counter() - ctx["t0"]
        drove = drive(server, mix, reqs, float(ctx["seconds"]),
                      ctx["trace_dir"])
        compiles = ctx["compiles"].n - compiles0
        red = reduce(ctx, server, drove)
        peak = harness.memory_peak_bytes(ctx["devices"])
        sample = [(t.req.prompt, t.out) for t in sample_for_check(
            red["ok"], ctx["seed"], int(mix.get("check_sample", 6)))]
    finally:
        server.close()
    del server, drove
    red.pop("ok")
    gc.collect()
    checks = {"requests_failed": float(red["failed"])}
    if sample:
        modes = ("f32",) + tuple(ctx.get("readings") or ())
        got = served_gaps(cfg, ctx["seed"], sample, modes=modes,
                          bucket=int(mix.get("check_bucket", 512)))
        extra = {m: {"served_logit_gap_max": got[m]} for m in modes[1:]}
        checks["served_logit_gap_max"] = got["program"]
        red["counters"]["checked_tokens"] = got["tokens"]
    else:
        checks["served_logit_gap_max"] = float("nan")
        extra = {}
    ok, shown, _ = harness.judge(checks, ctx["limits"])
    red["counters"]["compiles_in_window"] = compiles
    red["e2e"]["setup_s"] = setup_s
    return {"correct": ok, "attempted": red["attempted"],
            "failed": red["failed"], "checks": shown, "e2e": red["e2e"],
            "counters": red["counters"], "memory_peak_bytes": peak,
            "readings": extra}


def _altered(out: np.ndarray, cfg: dict) -> np.ndarray:
    """The fault a test plants: one served token changed where it is
    produced."""
    out = np.array(out)
    out[len(out) // 2] = (int(out[len(out) // 2]) + 1) % int(
        cfg["vocab_size"])
    return out


# ---------------------------------------------------------------- sweep


def sweep(ctx, rates: list[float]) -> list[dict]:
    """One server, one seeded population, each rate in turn (the knee is
    found once, when the cell is defined; benchmark/sweep.py)."""
    mix, cfg = ctx["mix"], ctx["cfg"]
    server = Server(ctx)
    points = []
    try:
        top = {**mix, "rate_rps": max(rates)}
        server.warm({**ctx, "mix": top})
        for rate in rates:
            at = {**mix, "rate_rps": rate}
            reqs = traffic.requests(at, ctx["seed"], ctx["seconds"],
                                    int(cfg["vocab_size"]))
            c0 = ctx["compiles"].n
            drove = drive(server, at, reqs, float(ctx["seconds"]), None)
            red = reduce({**ctx, "mix": at}, server, drove)
            red.pop("ok")
            points.append({"rate_rps": rate, **red["e2e"],
                           **red["counters"], "failed": red["failed"],
                           "attempted": red["attempted"],
                           "compiles_in_window": ctx["compiles"].n - c0})
            harness.log("sweep " + repr(points[-1]))
            server.taps.reset()
    finally:
        server.close()
    return points
