"""Serving runner: offers a mix to ``ContinuousEngine`` and records the
host clock at every token.

``ContinuousEngine.step()`` copies the step's logits to the host and
samples before it returns, so a token exists when ``step()`` returns: the
time read right after it is the token's time.  The runner keeps its own
due times (open loop), because the engine stamps requests only when they
are submitted.
"""
from __future__ import annotations

import time

import numpy as np

from . import traffic

__all__ = ["ServeRun", "warm_paged_shapes"]


def warm_paged_shapes(engine, write_counts, release_counts) -> None:
    """Compile the engine's shape-specialised host-side page moves.

    Scattering a finished prefill into its pages and resetting a released
    request's pages are eager operations whose shapes follow the request's
    page count, so each new count compiles (some tens of small programs).
    Run each count the mix can meet once on free pages (writing empty
    positions there changes nothing), so that no compilation falls inside
    the window."""
    import jax
    from repro.serve.cache import pack_prefill_pages
    from repro.serve.chunked import slice_cache

    kv, page = engine.kv, engine.page
    free = list(range(1, engine.max_blocks + 1))
    if kv.allocator.n_free < len(free):
        raise RuntimeError("page pool smaller than one request's table")
    temp = engine.model.init_cache(1, engine.chunk_cache, engine.cache_dtype,
                                   full_length=True)
    for nb in sorted(write_counts):
        kv.write_pages(pack_prefill_pages(slice_cache(temp, 0, nb * page),
                                          nb, page), free[:nb])
    for n in sorted(release_counts):
        kv.reset_blocks(free[:n])
    del temp
    jax.block_until_ready(kv.pools)


class ServeRun:
    """One run of a serving mix: ramp, settle, window, then the tail.

    ``records`` holds one dict per request the runner submitted:
    ``due`` (open loop; else the submit time), ``submit``, ``admit`` (the
    start of the step that admitted it), ``tokens`` (host time of each
    output token), ``rid`` and ``idx`` (its index in the mix)."""

    def __init__(self, engine, mix: dict, seed: int, vocab: int,
                 annotate=None):
        self.engine = engine
        self.mix = mix
        self.kind = mix["kind"]
        n = mix.get("requests_per_run", 4096)
        self.reqs = traffic.serve_requests(mix, seed, n, vocab)
        self.due = (traffic.arrival_offsets(mix, seed, n)
                    if self.kind == "poisson" else None)
        self.next = 0
        self.records: list[dict] = []
        self.live: dict[int, dict] = {}          # rid -> record
        self.steps: list[tuple] = []             # (t0, t1, admitted, chunks, rows)
        self.annotate = annotate

    # -- offering load -------------------------------------------------------
    def _submit(self, now: float, due: float) -> None:
        r = self.reqs[self.next]
        rid = self.engine.submit(r["prompt"], r["max_new_tokens"])
        rec = {"rid": rid, "idx": self.next, "due": due, "submit": now,
               "admit": None, "tokens": [], "prompt_len": len(r["prompt"]),
               "max_new": r["max_new_tokens"]}
        self.next += 1
        self.records.append(rec)
        self.live[rid] = rec

    def step(self, t_origin: float) -> None:
        """Offer what is due, run one engine step, stamp its tokens."""
        eng = self.engine
        now = time.perf_counter()
        if self.kind == "poisson":
            while self.next < len(self.reqs) and \
                    t_origin + self.due[self.next] <= now:
                self._submit(now, t_origin + self.due[self.next])
            if eng.idle:
                if self.next >= len(self.reqs):
                    raise RuntimeError("the mix ran out of requests")
                wait = t_origin + self.due[self.next] - now
                if wait > 0:
                    time.sleep(wait)
                return
        t0 = time.perf_counter()
        if self.annotate is not None:
            with self.annotate("engine.step"):
                eng.step()
        else:
            eng.step()
        t1 = time.perf_counter()
        tr = eng.step_trace[-1]
        self.steps.append((t0, t1, tr["admitted"], tr["prefill_chunks"],
                           tr["decode_rows"]))
        done = []
        for rid, rec in self.live.items():
            req = eng.requests[rid]
            if rec["admit"] is None and req.state != "QUEUED":
                rec["admit"] = t0
            new = len(req.generated) - len(rec["tokens"])
            rec["tokens"].extend([t1] * new)
            if req.state == "FINISHED":
                done.append(rid)
            elif req.state in ("FAILED", "EXPIRED", "CANCELLED"):
                raise RuntimeError(f"request {rid} ended {req.state}: "
                                   f"{req.error}")
        for rid in done:
            del self.live[rid]
            if self.kind == "closed":
                self._submit(t1, t1)

    def ramp(self, phase=lambda name: None) -> None:
        """Closed loop: one client joins per step until all are in, so the
        clients' requests do not all start (and prefill) at once."""
        if self.kind != "closed":
            return
        for i in range(self.mix["clients"]):
            self._submit(time.perf_counter(), time.perf_counter())
            self.step(0.0)
            if i == 0:
                phase("first step")

    def run_for(self, seconds: float, t_origin: float) -> float:
        """Step until ``seconds`` have passed; returns the end time (the end
        of the last step)."""
        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            self.step(t_origin)
        return time.perf_counter()

    def finish_due(self, t_end: float, t_origin: float,
                   limit_s: float = 60.0) -> None:
        """Keep offering load until every request due before ``t_end`` has
        its first token (at most ``limit_s`` more), so a late answer is
        counted late and not missing."""
        stop = time.perf_counter() + limit_s
        while time.perf_counter() < stop and any(
                not r["tokens"] for r in self.records if r["due"] < t_end):
            self.step(t_origin)

    def finish_some(self, k: int, t_origin: float,
                    limit_s: float = 60.0) -> None:
        """Keep offering load until ``k`` requests have finished (at most
        ``limit_s`` more), so the comparison has finished requests to
        sample even where the window closed before any did."""
        stop = time.perf_counter() + limit_s
        while len(self.engine.finished) < k and time.perf_counter() < stop:
            self.step(t_origin)

    # -- results ---------------------------------------------------------------
    def finished_records(self) -> list[dict]:
        return [r for r in self.records if r["rid"] in self.engine.finished]

    def served(self, rec: dict) -> tuple[np.ndarray, np.ndarray]:
        req = self.engine.finished[rec["rid"]]
        return (np.asarray(req.prompt, np.int32),
                np.asarray(req.generated, np.int32).reshape(-1))
