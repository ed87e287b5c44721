"""Closed-loop training: ``OnlineTrainer(sparse=True).train_step`` over
batches made from the seed, one static padded shape.

Set-up builds the trainer once and drives it through its first three steps on
distinct batches, through the same ``train_step`` call and feed as the
window. Those steps are what the check compares with the reference: each
step's loss, each leaf's first gradient as the optimizer got it (read back
from its state after step 1, and row by row for the arena), and each leaf's
change after step 3. The window then carries on with the same trainer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import loadgen
from bench.record import (Run, annotate, clock, compile_counter,
                          start_trace)

CHECKED_STEPS = 3


def batch_bodies(model, c: dict, mix: dict, seed: int, n: int) -> list:
    """The bodies of the first ``n`` distinct batches a run of ``seed``
    trains on, in order."""
    return [model.bodies(c, mix, mix["batch"], seed * 1000 + k)
            for k in range(n)]


class Train:
    """The trainer of one cell; ``model`` is the configuration's model
    module, and on a ``mesh`` the arena and its optimizer state are
    row-sharded over it."""

    def __init__(self, model, c: dict, mix: dict, seed: int, mesh=None):
        from repro.training import OnlineTrainer

        opt = c["optimizer"]
        if opt["arena"]["lr"] != 10 * opt["mlp"]["lr"]:
            raise ValueError("the program's trainer steps the arena at ten "
                             "times the MLP rate; the configuration says "
                             "otherwise")
        self.model, self.c, self.mix, self.seed = model, c, mix, seed
        self.max_l = loadgen.max_len(mix["bag"])
        self.bodies = batch_bodies(model, c, mix, seed,
                                   mix["distinct_batches"])
        self.batches = [model.train_batch(c, mix, b) for b in self.bodies]
        self.work = [model.train_work(c, b, mix["batch"])
                     for b in self.bodies]
        params = model.weights(c, seed, mesh)
        jax.block_until_ready(params)
        self.trainer = OnlineTrainer(model.program_config(c), params,
                                     max_l=self.max_l, lr=opt["mlp"]["lr"],
                                     sparse=True, mesh=mesh)
        del params
        self.k = 0
        self.reading_s = 0.0     # time spent reading for the check

    def step(self) -> None:
        with annotate("train_step"):
            self.trainer.train_step(self.batches[self.k])
        self.k = (self.k + 1) % len(self.batches)

    def first_steps(self) -> dict:
        """The checked steps; the first one compiles."""
        tr = self.trainer
        self.step()
        t = clock()
        out = self.model.first_step_readings(self.c, tr, self.bodies[0],
                                             self.max_l)
        self.reading_s = clock() - t
        for _ in range(CHECKED_STEPS - 1):
            self.step()
        t = clock()
        out.update(self.model.change_readings(self.c, self.seed, tr))
        self.reading_s += clock() - t
        return {"losses": list(tr.losses[:CHECKED_STEPS]), **out}

    def hlo_texts(self) -> list:
        """The compiled train step, as HLO text."""
        tr = self.trainer
        batch = {k: jnp.asarray(v) for k, v in self.batches[0].items()}
        return [tr._step.lower(tr.params, tr.opt_state, batch)
                .compile().as_text()]

    def checked_bodies(self) -> list:
        """The bodies of the checked steps, for the reference."""
        return self.bodies[:CHECKED_STEPS]

    def window(self, seconds: float, profile_dir: str = None) -> Run:
        """Whole steps until ``seconds`` have passed; the rate is taken
        over all of them and all their time."""
        steps = []
        if profile_dir:
            start_trace(profile_dir)
        with compile_counter() as compiles, annotate("window"):
            t0 = clock()
            while clock() - t0 < seconds:
                k = self.k
                s = clock() - t0
                self.step()
                steps.append((s, clock() - t0, self.mix["batch"], k))
        if profile_dir:
            jax.profiler.stop_trace()
        run = Run(config=self.c, traffic=self.mix, peaks={},
                  window_s=steps[-1][1], window_compiles=compiles[0],
                  steps=steps)
        for _, _, _, k in steps:
            run.add_work(self.work[k])
        return run

    def close(self):
        self.trainer = None
