"""LM cells: chat requests through the program's continuous Scheduler
(paged KV cache, decode-time SLA).

Set-up makes the weights, builds the scheduler and serves a few short
warm-up requests through admission, decode, copy-on-write and
retirement, so that every program the window uses is compiled. A
backlog cell then submits its whole pool and the window calls `step()`
until the first step that ends at or after `seconds`. An open-loop cell
submits each request when it falls due, calls `step()` while there is
work, and after the window stops arrivals and drains the requests that
fell due in it. Times are the harness's own clock: a token counts as
delivered when the `step()` that produced it returns.
"""
from __future__ import annotations

import time

import numpy as np

from bench import generate, program, reference, weights, window


class LMRunner:
    family = "lm"
    reference_function = "served_logits"

    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.ref = reference.named(cell, self.reference_function)
        self.traffic = cell.traffic
        self.reqs = {}       # rid -> record
        self.counters = {}

    def setup(self, seconds: float):
        import jax
        from repro.serving.api import SamplingParams, Scheduler

        cfg, tr = self.cell.config, self.traffic
        serve = cfg["serving"]
        arch = program.arch_config(cfg)
        mdl = program.model_module(arch)
        self.params = weights.make(lambda k: mdl.init(k, arch), self.seed,
                                   program.DTYPES[cfg["weight_dtype"]])
        self.SamplingParams = SamplingParams
        self.sched = Scheduler(
            arch, self.params, num_slots=int(serve["slots"]),
            max_len=int(serve["max_len"]), backend=cfg["backend"],
            decode_sla=True, paged=True,
            prefill_bucket=int(serve["prefill_bucket"]),
            compute_dtype=program.DTYPES[cfg["compute_dtype"]])
        self.bucket = int(serve["prefill_bucket"])
        self.max_len = int(serve["max_len"])
        rng = np.random.default_rng([self.seed, 4])
        warm = tr["warmup"]
        for _ in range(int(warm["requests"])):
            self.sched.submit(
                rng.integers(0, arch.vocab_size,
                             size=int(warm["prompt"])).astype(np.int32),
                SamplingParams(max_new_tokens=int(warm["output"])))
        while self.sched.has_work:
            self.sched.step()
        self.requests = generate.lm_requests(tr, self.seed,
                                             arch.vocab_size, seconds)
        self.open = tr["loop"] == "open"
        if not self.open:
            for r in self.requests:
                self._submit(r, None)
        self.clock_offset = time.time() - time.perf_counter()
        jax.block_until_ready(self.sched._live)

    def _submit(self, r, due):
        rid = self.sched.submit(
            r.prompt, self.SamplingParams(max_new_tokens=r.max_new))
        self.reqs[rid] = {"req": r, "due": due, "admit": None,
                          "tokens": [], "times": [], "done": False}
        return rid

    def _step(self, annotate):
        with annotate("bench.step"):
            events = self.sched.step()
        now = time.perf_counter()
        for ev in events:
            rec = self.reqs.get(ev.rid)
            if rec is None:
                continue
            if ev.kind == "token":
                rec["tokens"].append(int(ev.token))
                rec["times"].append(now)
            elif ev.kind == "start":
                rec["admit"] = ev.t - self.clock_offset
            elif ev.kind == "finish":
                rec["done"] = True
                rec["finish"] = now
        return now

    def run_window(self, seconds: float, annotate):
        sched = self.sched
        st0 = dict(vars(sched.stats))
        t0 = time.perf_counter()
        if not self.open:
            while True:
                now = self._step(annotate)
                if now - t0 >= seconds:
                    break
            t_end = now
        else:
            due = [t0 + r.due_s for r in self.requests]
            i = 0
            while True:
                now = time.perf_counter()
                with annotate("bench.submit"):
                    while i < len(due) and due[i] <= now:
                        self._submit(self.requests[i], due[i])
                        i += 1
                if sched.has_work:
                    self._step(annotate)
                elif i < len(due):
                    with annotate("bench.wait"):
                        time.sleep(max(0.0, due[i] - time.perf_counter()))
                else:
                    break
            t_end = t0 + seconds
        self.t0, self.t_end = t0, t_end
        st1 = dict(vars(sched.stats))
        self.counters = {k: st1[k] - st0[k] for k in st1
                         if isinstance(st1[k], (int, float))}
        self.window_tokens = sum(
            sum(1 for t in rec["times"] if t <= t_end)
            for rec in self.reqs.values())
        self.admitted_prompt_tokens = sum(
            len(rec["req"].prompt) for rec in self.reqs.values()
            if rec["admit"] is not None and rec["admit"] <= t_end)

    def end_to_end(self) -> dict:
        if not self.open:
            return {"lm_tokens_per_s": window.rate(
                self.window_tokens, self.t0, self.t_end)}
        recs = [{"due": r["due"], "tokens": r["times"]}
                for r in self.reqs.values()]
        ttfts, gaps = window.ttfts_and_gaps(recs)
        return {"ttft_p90_ms": 1e3 * window.nearest_rank(ttfts, 0.90),
                "itl_p95_ms": 1e3 * window.nearest_rank(gaps, 0.95)}

    def attempted(self):
        recs = list(self.reqs.values())
        if self.open:
            return len(recs), sum(not r["done"] for r in recs)
        admitted = [r for r in recs if r["admit"] is not None]
        return len(admitted), 0

    # -- correctness ---------------------------------------------------------
    def release(self):
        rng = np.random.default_rng([self.seed, 5])
        limit = float("inf") if self.open else self.t_end
        done = [r for r in self.reqs.values()
                if r["done"] and r["finish"] <= limit]
        k = int(self.cell.settings["check_requests"])
        done.sort(key=lambda r: -len(r["tokens"]))
        pick = done[:1]
        rest = done[1:]
        for idx in rng.permutation(len(rest))[:max(0, k - 1)]:
            pick.append(rest[int(idx)])
        self.samples = [(r["req"].prompt, list(r["tokens"])) for r in pick]
        del self.sched

    def _sequence(self, prompt, served):
        toks = np.zeros((self.max_len,), np.int32)
        toks[self.bucket - len(prompt):self.bucket] = prompt
        fed = served[:-1]
        toks[self.bucket:self.bucket + len(fed)] = fed
        return toks

    def check(self, control: bool = False) -> dict:
        """Widest gap by which a served token's reference logit lies below
        the reference's best logit at that position, over the sampled
        requests. With control=True the served tokens are replaced by the
        tokens that the float8 reference puts first."""
        arch = self.cell.config["model"]
        worst, gaps = 0.0, []
        for prompt, served in self.samples:
            toks = self._sequence(prompt, served)
            lg = np.asarray(self.ref.served_logits(self.params, arch, toks,
                                                   self.bucket))
            rows = lg[:len(served)]
            if control:
                ctl = np.asarray(self.ref.served_logits(
                    self.params, arch, toks, self.bucket, inputs="float8"))
                pick = np.argmax(ctl[:len(served)], axis=-1)
            else:
                pick = np.asarray(served)
            gap = rows.max(axis=-1) - rows[np.arange(len(served)), pick]
            worst = max(worst, float(gap.max()))
            gaps.append(gap)
        gaps = np.concatenate(gaps)
        return {"lm_logit_gap": worst,
                "lm_logit_gap_mean": float(gaps.mean()),
                "lm_argmax_miss": float(np.mean(gaps > 0))}
