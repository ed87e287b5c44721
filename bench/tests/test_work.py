"""FLOP and byte counts against hand counts, for dlrm5 and for a small
configuration whose dense head outweighs its gather."""
import json

from bench import work
from bench.tests.helpers import ROOT

C5 = json.loads((ROOT / "bench/configs/dlrm5.json").read_text())
# five tables of two gathers and wide MLPs: the head binds by FLOPs
C6 = {"n_tables": 5, "rows_per_table": 200_000, "emb_dim": 32,
      "lookups_per_table": 2, "dense_features": 13,
      "bottom_mlp": [1024, 512, 32], "top_mlp": [1024, 512, 1]}


def test_head_flops_by_hand():
    # dlrm5: bottom 13-512-256-64-16, 51 features -> 1275 pairs of 16-dim
    # dots, top (16 + 1275)-512-256-1
    bottom = 2 * (13 * 512 + 512 * 256 + 256 * 64 + 64 * 16)
    top = 2 * (1291 * 512 + 512 * 256 + 256 * 1)
    assert work.head_flops(C5) == bottom + 1275 * 32 + top
    # the heavy head: 6 features -> 15 pairs, top (32 + 15)-1024-512-1
    bottom = 2 * (13 * 1024 + 1024 * 512 + 512 * 32)
    top = 2 * (47 * 1024 + 1024 * 512 + 512 * 1)
    assert work.head_flops(C6) == bottom + 15 * 64 + top
    assert work.head_flops(C6) == 2_254_784


def test_gather_bytes_by_hand():
    # 80 ids of a bag in each of 50 tables: 4000 rows of 64 B and 4000 ids
    # of 4 B, 50 bag sums of 64 B
    flops, nbytes = work.gather(C5, 80 * 50, 1)
    assert nbytes == 4000 * (64 + 4) + 50 * 64
    assert flops == 4000 * 16
    _, nbytes = work.gather(C6, 10, 1)
    assert nbytes == 10 * 132 + 5 * 128


def test_head_bytes_count_weights_once_per_batch():
    w5 = 4 * ((13 * 512 + 512) + (512 * 256 + 256) + (256 * 64 + 64)
              + (64 * 16 + 16)
              + (1291 * 512 + 512) + (512 * 256 + 256) + (256 + 1))
    assert work.weight_bytes(C5) == w5
    _, one = work.head(C5, 1)
    _, two = work.head(C5, 2)
    assert two - one == 4 * (13 + 50 * 16 + 1)


def test_least_time_picks_the_binding_bound():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # the gather is bound by bytes; the heavy head at a large batch by FLOPs
    w = work.Work()
    w.add(*work.gather(C5, 4000, 1))
    assert w.least_s(peaks) == w.bytes / 819e9 > w.flops / 197e12
    h = work.Work()
    h.add(*work.head(C6, 4096))
    assert h.least_s(peaks) == h.flops / 197e12 > h.bytes / 819e9


def test_train_step_counts_forward_backward_and_update():
    parts = work.train_step(C5, 1000, 600, 2)
    assert parts["gather"] == work.gather(C5, 1000, 2)
    assert parts["head"][0] == 3 * 2 * work.head_flops(C5)
    assert parts["update"][1] > 600 * 2 * (64 + 4)
