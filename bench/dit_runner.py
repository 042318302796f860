"""DiT cells: denoise requests through the program's DiffusionScheduler.

Set-up makes the weights, builds the scheduler, runs every slot through
one admission and retirement (so that every program the window uses is
compiled and loaded), then fills the slots with staggered requests and
runs one tick. The window calls `step()` until the first tick that ends
at or after `seconds`. The answers checked against the reference are
those that start from the benchmark's own noise: each request admitted
in the window, after its admission step and first tick.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench import generate, program, reference, weights, window


class DiTRunner:
    family = "dit"
    reference_function = "euler_steps"

    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.ref = reference.named(cell, self.reference_function)
        self.traffic = cell.traffic
        self.moved = set()     # rids the window advanced
        self.admitted = []     # (rid, slot, steps, latents after the step)
        self.submitted = {}    # rid -> generate.DiTRequest
        self.counters = {}

    # -- set-up ------------------------------------------------------------
    def setup(self, seconds: float):
        import jax
        from repro.serving.diffusion import DenoiseParams, DiffusionScheduler

        cfg, tr = self.cell.config, self.traffic
        t = [time.perf_counter()]
        arch = program.arch_config(cfg)
        mdl = program.model_module(arch)
        self.params = weights.make(lambda k: mdl.init(k, arch), self.seed,
                                   program.DTYPES[cfg["weight_dtype"]])
        jax.block_until_ready(self.params)
        t.append(time.perf_counter())
        dtype = program.DTYPES[cfg["compute_dtype"]]
        self.sched = DiffusionScheduler(
            arch, self.params, num_slots=int(tr["slots"]),
            seq_len=int(tr["seq_len"]), backend=cfg["backend"],
            compute_dtype=dtype)
        fill, backlog = generate.dit_requests(tr, self.seed, arch.patch_dim)
        self.conds = generate.dit_conds(tr, self.seed, arch.cond_len,
                                        arch.d_model)
        self.DenoiseParams = DenoiseParams
        one = float(tr["t_start"]) / int(tr["num_steps"])
        # every slot admits and retires a one-step request: compiles the
        # admission and the read-out of each slot before the window
        for r in fill:
            self._submit(generate.DiTRequest(1, one, r.latent, r.cond))
        self.sched.step()
        t.append(time.perf_counter())
        for r in fill + backlog:
            self._submit(r)
        self.sched.step()  # fills the slots and compiles the tick
        jax.block_until_ready(self.sched._lat)
        t.append(time.perf_counter())
        print("setup phases (weights, admissions, fill and tick) "
              f"{[b - a for a, b in zip(t, t[1:])]!r} s", file=sys.stderr)

    def _submit(self, r):
        rid = self.sched.submit(
            r.latent, self.DenoiseParams(num_steps=r.num_steps,
                                         t_start=r.t_start),
            cond=self.conds[r.cond])
        self.submitted[rid] = r
        return rid

    # -- the window ----------------------------------------------------------
    def run_window(self, seconds: float, annotate):
        import jax

        sched = self.sched
        st0 = dict(vars(sched.stats))
        ticks = 0
        t0 = time.perf_counter()
        while True:
            before = {j: (r.rid, r.steps_done)
                      for j, r in enumerate(sched._slots) if r is not None}
            with annotate("bench.step"):
                events = sched.step()
                jax.block_until_ready(sched._lat)
            t_end = time.perf_counter()
            ticks += 1
            for slot, rid, first, n in self._answers(before, events):
                self.moved.add(rid)
                if first == 0:
                    self.admitted.append((rid, slot, n, sched._lat))
            if t_end - t0 >= seconds:  # the tick that crosses ends it
                break
        self.t0, self.t_end = t0, t_end
        self.ticks = ticks
        st1 = dict(vars(sched.stats))
        self.counters = {k: st1[k] - st0[k] for k in st1
                         if isinstance(st1[k], (int, float))}

    def _answers(self, before, events):
        """(slot, rid, first_step, n_steps) of every slot the step moved:
        a slot that only ticked moved one step from its state before the
        call; an admitted request moved from its noise through the
        admission step and, unless already finished, the tick."""
        moved = {}
        started = set()
        for ev in events:
            if ev.kind == "step":
                moved[ev.rid] = moved.get(ev.rid, 0) + 1
            elif ev.kind == "start":
                started.add(ev.rid)
        slot_of = {rid: j for j, (rid, _) in before.items()}
        for j, r in enumerate(self.sched._slots):
            if r is not None and r.rid in started:
                slot_of[r.rid] = j
        out = []
        for rid, n in moved.items():
            if rid in started:
                first = 0
                if rid not in slot_of:   # finished within its admission
                    continue
            else:
                first = dict(before.values())[rid]
            out.append((slot_of[rid], rid, first, n))
        return out

    def end_to_end(self) -> dict:
        tokens = self.counters["denoise_steps"] * int(self.traffic["seq_len"])
        return {"dit_tokens_per_s": window.rate(tokens, self.t0, self.t_end)}

    def attempted(self):
        return len(self.moved), 0

    # -- correctness ---------------------------------------------------------
    def release(self):
        """Drop the program's state; keep the answers that start from the
        benchmark's own noise (every request admitted in the window)."""
        self.samples = [(self.submitted[rid], n,
                         np.asarray(lat[slot], np.float32))
                        for rid, slot, n, lat in self.admitted]
        self.admitted = []
        del self.sched

    def check(self, control: bool = False) -> dict:
        """Over the requests admitted in the window, each from its seeded
        noise through its admission step and first tick, with every error
        relative to the reference's update ||x_reference - x_noise||:

        - `dit_step_rel_err`: ||x - x_reference||, x the program's answer
          (with control=True, the reference's own from inputs one
          precision below the configuration's `product_inputs`);
        - `dit_step_floor_err`: the same for the reference computed from
          `product_inputs` (the precision the configuration states):
          what rounding at that precision alone moves the answer;
        - `dit_step_excess_err` (compared): sqrt(max(0, rel^2 -
          floor^2)), the error beyond that floor;
        - `dit_step_max_err`: the largest single-element error in units
          of the update's RMS; `dit_token_err_median`: the median over
          tokens of each token's relative error.

        Each is the largest over the answers. No admitted request reads
        as an infinite error."""
        from bench.reference.numerics import BELOW

        arch = self.cell.config["model"]
        stated = self.cell.config["product_inputs"]
        out = dict.fromkeys(("dit_step_excess_err", "dit_step_rel_err",
                             "dit_step_floor_err", "dit_step_max_err",
                             "dit_token_err_median"), 0.0)
        if not self.samples:
            return {k: float("inf") for k in out}
        for req, n, x_prog in self.samples:
            args = (self.params, arch, req.latent, req.t_start,
                    req.num_steps, 0, n, self.conds[req.cond])
            x_ref = np.asarray(self.ref.euler_steps(*args))
            x_floor = np.asarray(self.ref.euler_steps(*args, inputs=stated))
            if control:
                x_prog = np.asarray(self.ref.euler_steps(
                    *args, inputs=BELOW[stated]))
            upd = x_ref - req.latent
            scale = max(float(np.linalg.norm(upd)), 1e-30)
            rel = float(np.linalg.norm(x_prog - x_ref)) / scale
            floor = float(np.linalg.norm(x_floor - x_ref)) / scale
            rms = scale / np.sqrt(upd.size)
            per_token = (np.linalg.norm(x_prog - x_ref, axis=-1)
                         / np.maximum(np.linalg.norm(upd, axis=-1), 1e-30))
            got = {"dit_step_excess_err": max(0.0, rel * rel
                                              - floor * floor) ** 0.5,
                   "dit_step_rel_err": rel, "dit_step_floor_err": floor,
                   "dit_step_max_err":
                       float(np.max(np.abs(x_prog - x_ref))) / rms,
                   "dit_token_err_median": float(np.median(per_token))}
            out = {k: max(out[k], got[k]) for k in out}
        return out
