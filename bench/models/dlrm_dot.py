"""DLRM with the dot interaction (Naumov et al., arXiv:1906.00091;
github.com/facebookresearch/dlrm): bottom MLP, pairwise dots of its output
and the bag sums, top MLP. The model of every configuration that names
none, ``dlrm5`` among them.

A model module gives the harness, for a configuration ``c`` (its JSON
dict):

* ``program_config(c)``: the program's ``DLRMConfig``;
* ``weights(c, seed, mesh=None)``: every weight from the seed, in the
  program's tree, on the device (row-sharded over ``mesh`` where given);
* ``bodies(c, mix, n, seed)``: ``n`` request bodies (``loadgen.Bodies``);
* ``train_batch(c, mix, bodies)``: the program's training batch;
* ``reference_ctr(c, seed, bodies, sel, max_l, precision)``: the plain
  reference's CTRs of requests ``sel``;
* ``reference_train(c, seed, bodies, max_l, precision, half_batch)``: the
  reference's first steps on those batches and their readings (losses,
  first gradient norms, row-gradient norms, change norms);
* ``first_step_readings(c, trainer, bodies, max_l)`` and
  ``change_readings(c, seed, trainer)``: the same readings of the
  program's trainer, after its first step and after its checked steps;
* ``serve_work(c, n_ids, n_requests)`` and ``train_work(c, bodies,
  batch)``: the least work of a serve batch and of a train step
  (``{part: (flops, bytes)}``).

``precision`` is ``"highest"`` or the control's ``"high"``.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench import loadgen, reference, work
from bench import weights as _weights

bodies = loadgen.bodies
train_batch = loadgen.train_batch
weights = _weights.make
serve_work = work.serve_batch


def program_config(c: dict):
    from repro.configs.base import DLRMConfig
    return DLRMConfig(
        name=c["name"], n_tables=c["n_tables"],
        rows_per_table=c["rows_per_table"], emb_dim=c["emb_dim"],
        lookups_per_table=c["lookups_per_table"],
        dense_features=c["dense_features"],
        bottom_mlp=tuple(c["bottom_mlp"]), top_mlp=tuple(c["top_mlp"]),
        dtype=c["dtype"])


def reference_ctr(c: dict, seed: int, bodies, sel, max_l: int,
                  precision: str = "highest") -> np.ndarray:
    return reference.ctr(c, _weights.make(c, seed), bodies, sel, precision,
                         max_l=max_l)


def reference_train(c: dict, seed: int, bodies, max_l: int,
                    precision: str = "highest",
                    half_batch: bool = False) -> dict:
    """With ``half_batch``, the fault of a step that leaves the second
    half of its batch out; its row-gradient norms are read on the rows
    the whole first batch reads."""
    batches = [reference.train_batch_of(b, max_l) for b in bodies]
    if not half_batch:
        return reference.train(c, seed, batches, precision)
    rows = reference.touched_rows(batches[0], c["n_tables"],
                                  c["rows_per_table"])
    return reference.train(c, seed, [reference.half_batch(b, c["n_tables"])
                                     for b in batches], precision,
                           grad_rows=rows)


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


def first_step_readings(c: dict, trainer, bodies, max_l: int) -> dict:
    """Each leaf's first gradient as the optimizer got it, read back from
    its state after step 1, and row by row for the arena on every row
    the first batch reads."""
    state = trainer.opt_state
    first = reference.train_batch_of(bodies, max_l)
    return {
        "grad_norms": [arena_grad_norm(c, state)] + mlp_grad_norms(c, state),
        "row_grad_norms": reference.row_norms(
            state["arena"]["acc"],
            reference.touched_rows(first, c["n_tables"],
                                   c["rows_per_table"]),
            c["emb_dim"])}


def change_readings(c: dict, seed: int, trainer) -> dict:
    return {"change_norms": reference.change_norms(c, seed, trainer.params)}


def train_work(c: dict, bodies, batch: int) -> dict:
    """One step on ``bodies``: its ids and the distinct rows they name."""
    table = np.repeat(np.arange(bodies.lens.size) % c["n_tables"],
                      bodies.lens.reshape(-1))
    flat = bodies.ids.astype(np.int64) + table * c["rows_per_table"]
    return work.train_step(c, bodies.ids.size, np.unique(flat).size, batch)
