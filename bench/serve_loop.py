"""Drive the program's serving engine with a cell's traffic and time it.

The engine is ``repro.serve.engine.ContinuousBatchingEngine``, built at the
configuration's published widths and engine shape, greedy, no EOS, driven
only through ``submit`` and ``step``. The host clock is read around every
``step``; a token counts as served at the end of the step that made it
visible, and towards the window when that step started inside it.

What each step did, for the cost model, is read from the scheduler's state
around it: the (start, length) of every prefill chunk and the context of
every slot the decode step served. In the traced run the harness's own
phases are ``TraceAnnotation`` spans that the device trace lines up with;
spans inside the engine are the program's to add.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

import loadgen
from trace_reduce import WINDOW_SPAN

SPAN_NAMES = ("engine_step", "submit", "arrival_wait", "harness")


# ----------------------------------------------------------------- build ----
def model_config(config: dict, published: dict):
    """The program's config: its arch with the file's overrides (a JSON
    list is a tuple, such as a ``block_pattern``), checked against the
    published sizes field by field (``published``: program field ->
    config key, the model module's ``PUBLISHED``)."""
    from repro.configs.registry import get_config

    def tup(v):
        return tuple(v) if isinstance(v, list) else v

    prog = config["program"]
    cfg = get_config(prog["arch"], **{
        k: tup(v) for k, v in prog.get("overrides", {}).items()})
    for field, key in published.items():
        if getattr(cfg, field) != tup(config[key]):
            raise SystemExit(f"{config['name']}: the program's {field} "
                             f"{getattr(cfg, field)} is not the published "
                             f"{key} {config[key]}")
    return cfg


def build_engine(config: dict, published: dict, params):
    from repro.configs.base import ServeConfig
    from repro.serve.engine import ContinuousBatchingEngine
    cfg = model_config(config, published)
    scfg = ServeConfig(score_norm=cfg.score_norm,
                       max_slots=config["max_slots"], **config["engine"])
    return ContinuousBatchingEngine(cfg, scfg, params)


def warm_engine(engine, hlo: dict | None = None) -> dict:
    """Compile every program the window drives; seconds per step. Where
    ``hlo`` is given, each step's optimized HLO text is kept in it."""
    import jax.numpy as jnp
    secs = {}
    for name in ("prefill", "decode"):
        t0 = time.perf_counter()
        compiled = engine.compile_step(name)
        if hlo is not None:
            hlo[name] = compiled.as_text()
        secs[name] = time.perf_counter() - t0
    t0 = time.perf_counter()
    zero = jnp.asarray(0, jnp.int32)
    # slot 0 is free and page 0 unowned before any admission: a reset, an
    # index pin to 0 and a self-copy change nothing
    engine.caches = engine._reset(engine.caches, zero)
    if engine._set_index is not None:
        engine.caches = engine._set_index(engine.caches, zero, zero)
        engine.caches = engine._copy_page(engine.caches, zero, zero)
    jax.block_until_ready(engine.caches)
    secs["reset_index_copy"] = time.perf_counter() - t0
    return secs


# --------------------------------------------------------------- records ----
@dataclasses.dataclass
class Records:
    due: dict = dataclasses.field(default_factory=dict)
    admitted: dict = dataclasses.field(default_factory=dict)
    first: dict = dataclasses.field(default_factory=dict)
    last: dict = dataclasses.field(default_factory=dict)
    count: dict = dataclasses.field(default_factory=dict)
    gaps: list = dataclasses.field(default_factory=list)
    window_tokens: int = 0
    window_steps: int = 0
    t_w0: float = float("inf")
    t_w1: float = float("inf")
    t_end: float = 0.0                     # end of the last counted step
    late: list = dataclasses.field(default_factory=list)
    submit_failures: int = 0
    # what each step did: the cost model's input
    decode_log: list = dataclasses.field(default_factory=list)
    prefill_log: list = dataclasses.field(default_factory=list)


class Runner:
    """One engine, one request stream, the host-side timing."""

    def __init__(self, engine, mix: dict, cell: dict, seed: int,
                 rate_per_s: float | None = None):
        self.engine = engine
        self.mix, self.cell, self.seed = mix, cell, seed
        self.vocab = engine.cfg.vocab_size
        self.rec = Records()
        self.req_of: dict[int, loadgen.Request] = {}
        self.active: set[int] = set()
        self.finished: list[int] = []
        self.open = mix["loop"] == "open"
        self.slots = engine.scfg.max_slots
        self.rate = float(rate_per_s or cell.get("rate_per_s") or 0)
        self._pending: list[tuple[loadgen.Request, float]] = []  # open
        self._stream: list[loadgen.Request] = []                 # closed
        self._index = 0
        self.traced = False

    # ------------------------------------------------------------ stream ----
    def _new_set(self, n: int, **kw) -> list[loadgen.Request]:
        reqs = loadgen.request_set(self.mix, self.cell, self._index, n, **kw)
        self._index += n
        return reqs

    def _schedule(self, t0: float, seconds: float):
        """Open loop: a set of requests due over [t0, t0 + seconds)."""
        reqs = self._new_set(loadgen.open_count(self.rate, seconds),
                             seconds=seconds)
        self._pending += [(r, t0 + r.due_s) for r in reqs]

    def _submit(self, req: loadgen.Request, due: float):
        with self._span("submit"):
            prompt = loadgen.prompt_tokens(self.seed, req.index,
                                           req.prompt_len, self.vocab)
            try:
                uid = self.engine.submit(prompt, req.output_len)
            except ValueError:
                self.rec.submit_failures += 1
                return
        self.rec.due[uid] = due
        self.req_of[uid] = req

    def _top_up(self):
        want = self.slots + int(self.mix["outstanding_beyond_slots"])
        while len(self.req_of) - len(self.finished) < want:
            if not self._stream:
                self._stream = self._new_set(self.slots)
            self._submit(self._stream.pop(0), time.perf_counter())

    def _arrivals(self, now: float):
        """Submit every request due by ``now``; the next due time."""
        while self._pending:
            req, due = self._pending[0]
            if due > now:
                return due
            self._submit(req, due)
            self.rec.late.append(now - due)
            self._pending.pop(0)
        return None

    # -------------------------------------------------------------- loop ----
    def warm(self, seconds: float):
        """Set-up's share of the traffic: an open loop's warm phase, or a
        closed loop's first wave stepped until every request of it has its
        prompt prefilled (it is decoding)."""
        if self.open:
            t0 = time.perf_counter()
            self._schedule(t0, seconds)
            self.run_until(t0 + seconds, count=False)
            return
        self._stream = self._new_set(self.slots, part_served=True)
        self._top_up()
        wave = set(list(self.req_of)[:self.slots])
        while any(u not in self.rec.first for u in wave):
            self._step(False)

    def run_until(self, t_stop: float, *, count: bool):
        """Step the engine until the host clock passes ``t_stop``."""
        while True:
            now = time.perf_counter()
            if now >= t_stop:
                return
            if self.open:
                nxt = self._arrivals(now)
                if not self.engine.scheduler.has_work():
                    wait = min(nxt if nxt is not None else t_stop,
                               t_stop) - now
                    if wait > 0:
                        with self._span("arrival_wait"):
                            time.sleep(wait)
                    continue
            self._step(count)

    def _step(self, count: bool):
        before = self._snapshot() if count else None
        t0 = time.perf_counter()
        with self._span("engine_step"):
            self.engine.step()
        t1 = time.perf_counter()
        with self._span("harness"):
            if count:
                self._log_work(before)
            self._harvest(t0, t1, count)
            if not self.open:
                self._top_up()

    def _snapshot(self) -> dict:
        sch = self.engine.scheduler
        dec = {i for i, _ in sch.decoding()}
        return {st.request.uid: (st.filled, len(st.generated), i in dec)
                for i, st in enumerate(sch.slots) if st is not None}

    def _log_work(self, before: dict):
        """What a window step did, from the scheduler's state around it:
        each prefill chunk's (start, length, completes the prompt), and the
        context of each slot the decode step served."""
        eng = self.engine
        now = {st.request.uid: st for st in eng.scheduler.slots
               if st is not None}
        ctx = []
        for uid in before.keys() | now.keys():
            plen = self.req_of[uid].prompt_len
            if uid in now:
                st = now[uid]
                filled, ngen = st.filled, len(st.generated)
            elif uid in eng.results:
                filled, ngen = plen, len(eng.results[uid])
            else:
                continue
            if uid in before:
                f0, g0, dec = before[uid]
            elif uid in now:                # admitted by this step
                f0, g0, dec = now[uid].prefix_cached, 0, False
            else:
                continue
            if dec:
                ctx.append(f0 + g0)
                continue
            if filled > f0:
                self.rec.prefill_log.append((f0, filled - f0, filled == plen))
            if filled == plen and ngen >= 2:
                ctx.append(plen + 1)
        if ctx:
            self.rec.decode_log.append(ctx)

    def _harvest(self, t0: float, t1: float, count: bool):
        rec = self.rec
        live = {}
        for st in self.engine.scheduler.slots:
            if st is not None:
                live[st.request.uid] = len(st.generated)
        for uid in live:
            if uid not in rec.admitted:
                rec.admitted[uid] = t0
        for uid in self.active - live.keys():
            live[uid] = len(self.engine.results[uid])
            self.finished.append(uid)
        for uid, n in live.items():
            prev = rec.count.get(uid, 0)
            if n <= prev:
                continue
            if prev == 0:
                rec.first[uid] = t1
            elif count:
                rec.gaps.append(t1 - rec.last[uid])
            if count:
                rec.gaps.extend([0.0] * (n - prev - 1))
                rec.window_tokens += n - prev
            rec.last[uid] = t1
            rec.count[uid] = n
        self.active = {u for u in live if u not in self.engine.results}
        if count:
            rec.window_steps += 1
            rec.t_end = t1

    def window(self, seconds: float):
        """The measured window: ``seconds`` of host clock from now; an open
        loop's window set arrives over it."""
        rec = self.rec
        rec.t_w0 = time.perf_counter()
        rec.t_w1 = rec.t_w0 + seconds
        if self.open:
            self._schedule(rec.t_w0, seconds)
        with self._span(WINDOW_SPAN):
            self.run_until(rec.t_w1, count=True)
        if self.open:
            # a request due in the window's last step arrived in it too
            self._arrivals(rec.t_w1)

    def _span(self, name):
        if not self.traced:
            return _NULL
        return jax.profiler.TraceAnnotation(name)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


# ------------------------------------------------------------ statistics ----
def percentile(values, q):
    return float(np.percentile(np.asarray(values, float), q)) if len(
        values) else None


def window_stats(runner: Runner) -> dict:
    """End-to-end numbers and the harness's own per-layer numbers."""
    rec = runner.rec
    t_end = max(rec.t_end, rec.t_w1)
    secs = rec.t_end - rec.t_w0 if rec.t_end > rec.t_w0 else None
    due_in = [u for u, d in rec.due.items() if rec.t_w0 <= d < rec.t_w1]
    ttft = [(rec.first[u] if u in rec.first and rec.first[u] <= t_end
             else t_end) - rec.due[u] for u in due_in]
    waits = [(rec.admitted[u] if u in rec.admitted else t_end) - rec.due[u]
             for u in due_in]
    return {
        "window_s": secs,
        "window_tokens": rec.window_tokens,
        "window_steps": rec.window_steps,
        "requests_due": len(due_in),
        "requests_first_token": sum(1 for u in due_in if u in rec.first),
        "ttft_p50_ms": _ms(percentile(ttft, 50)),
        "ttft_p95_ms": _ms(percentile(ttft, 95)),
        "ttft_mean_ms": _ms(np.mean(ttft) if ttft else None),
        "itl_p50_ms": _ms(percentile(rec.gaps, 50)),
        "itl_p95_ms": _ms(percentile(rec.gaps, 95)),
        "itl_p99_ms": _ms(percentile(rec.gaps, 99)),
        "itl_mean_ms": _ms(np.mean(rec.gaps) if rec.gaps else None),
        "itl_max_ms": _ms(max(rec.gaps) if rec.gaps else None),
        "gaps": len(rec.gaps),
        "output_tok_s": rec.window_tokens / secs if secs else None,
        "queue_wait_p50_ms": _ms(percentile(waits, 50)),
        "generator_late_p50_ms": _ms(percentile(rec.late, 50)),
        "generator_late_max_ms": _ms(max(rec.late) if rec.late else None),
        "backlog_at_end": runner.engine.scheduler.queue_depth,
    }


def _ms(x):
    return None if x is None else 1000.0 * float(x)
