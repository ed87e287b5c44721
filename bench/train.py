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
import numpy as np

from bench import loadgen, reference, weights, work
from bench.record import (Run, annotate, clock, compile_counter,
                          start_trace)
from bench.serve import dlrm_config

CHECKED_STEPS = 3


def arena_grad_norm(c: dict, state) -> float:
    """||g|| of the arena from row-wise Adagrad's accumulator after one
    step, which holds mean(g_row^2) for every row."""
    acc = state["arena"]["acc"]
    return float(jnp.sqrt(c["emb_dim"] * jnp.sum(acc.astype(jnp.float32))))


def mlp_grad_norms(c: dict, state) -> list:
    """||g|| per MLP leaf from AdamW's first moment after one step,
    m = (1 - b1) g."""
    m = state["mlp"]["m"]
    b1 = c["optimizer"]["mlp"]["b1"]
    tree = {"bottom": m["bottom"], "top": m["top"]}
    return [float(jnp.sqrt(jnp.sum(jnp.square(x)))) / (1.0 - b1)
            for x in reference.leaves(tree)[1:]]


def batch_bodies(c: dict, mix: dict, seed: int, n: int) -> list:
    """The bodies of the first ``n`` distinct batches a run of ``seed``
    trains on, in order."""
    return [loadgen.bodies(c, mix, mix["batch"], seed * 1000 + k)
            for k in range(n)]


class Train:
    def __init__(self, c: dict, mix: dict, seed: int):
        from repro.training import OnlineTrainer

        opt = c["optimizer"]
        if opt["arena"]["lr"] != 10 * opt["mlp"]["lr"]:
            raise ValueError("the program's trainer steps the arena at ten "
                             "times the MLP rate; the configuration says "
                             "otherwise")
        self.c, self.mix, self.seed = c, mix, seed
        self.bodies = batch_bodies(c, mix, seed, mix["distinct_batches"])
        self.batches = [loadgen.train_batch(c, mix, b) for b in self.bodies]
        rows = c["rows_per_table"]
        self.counts = []
        for b in self.bodies:
            table = np.repeat(np.arange(b.lens.size) % c["n_tables"],
                              b.lens.reshape(-1))
            flat = b.ids.astype(np.int64) + table * rows
            self.counts.append((b.ids.size, np.unique(flat).size))
        params = weights.make(c, seed)
        jax.block_until_ready(params)
        self.trainer = OnlineTrainer(dlrm_config(c), params,
                                     max_l=mix["bag"]["max"],
                                     lr=opt["mlp"]["lr"], sparse=True)
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
        grad_norms = ([arena_grad_norm(self.c, tr.opt_state)]
                      + mlp_grad_norms(self.c, tr.opt_state))
        first = reference.train_batch_of(self.bodies[0],
                                          self.mix["bag"]["max"])
        row_grads = reference.row_norms(
            tr.opt_state["arena"]["acc"],
            reference.touched_rows(first, self.c["n_tables"],
                                   self.c["rows_per_table"]),
            self.c["emb_dim"])
        self.reading_s = clock() - t
        for _ in range(CHECKED_STEPS - 1):
            self.step()
        t = clock()
        change = reference.change_norms(self.c, self.seed, tr.params)
        self.reading_s += clock() - t
        return {"losses": list(tr.losses[:CHECKED_STEPS]),
                "grad_norms": grad_norms, "row_grad_norms": row_grads,
                "change_norms": change}

    def hlo_texts(self) -> list:
        """The compiled train step, as HLO text."""
        tr = self.trainer
        batch = {k: jnp.asarray(v) for k, v in self.batches[0].items()}
        return [tr._step.lower(tr.params, tr.opt_state, batch)
                .compile().as_text()]

    def reference_batches(self) -> list:
        return [reference.train_batch_of(b, self.mix["bag"]["max"])
                for b in self.bodies[:CHECKED_STEPS]]

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
        for _, _, b, k in steps:
            n_ids, n_unique = self.counts[k]
            run.add_work(work.train_step(self.c, n_ids, n_unique, b))
        return run

    def close(self):
        self.trainer = None
