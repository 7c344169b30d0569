"""Operations and bytes from shapes, against a hand count for olmo-1b
and against the parameters the program's initialiser lays out."""
import json

import numpy as np
import pytest

from chipbench import costs, weights
from conftest import BENCH

OLMO_1B = json.loads((BENCH / "configs" / "olmo-1b.json").read_text())["arch"]

# per layer: q, k, v, o of 2048 x 2048 and three 2048 x 8192 MLP matrices
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 8192            # 67,108,864
HEAD = 2048 * 50304                                   # 103,022,592
N = 16 * LAYER + HEAD                                 # 1,176,764,416
PAIR = 4 * 16 * 2048                                  # q.k and p.v, all layers


def test_olmo_1b_hand_count():
    assert costs.layer_matmul_params(OLMO_1B) == LAYER == 67_108_864
    assert costs.matmul_params(OLMO_1B) == N == 1_176_764_416
    assert costs.param_count(OLMO_1B) == N           # tied: head = embedding
    assert costs.kv_bytes_per_token(OLMO_1B) == 2 * 16 * 16 * 128 * 2


def test_olmo_1b_step_counts():
    assert costs.decode_flops(OLMO_1B, 100) == 2 * N + PAIR * 100
    assert costs.prefill_flops(OLMO_1B, 3) == \
        2 * 16 * LAYER * 3 + PAIR * 6 + 2 * HEAD
    assert costs.train_flops_per_token(OLMO_1B, 2048) == \
        6 * N + 3 * PAIR * 2049 // 2
    flops, nbytes = costs.decode_step_cost(OLMO_1B, [10, 20])
    assert flops == 4 * N + PAIR * 30
    kvb = 131072
    assert nbytes == 2 * N + (9 + 19) * kvb + 2 * kvb


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.roofline_seconds(1000, 50, peak) == 10.0
    assert costs.roofline_seconds(100, 50, peak) == 5.0


@pytest.mark.parametrize("name", ["olmo-1b", "olmo-7b"])
def test_param_count_matches_program_layout(name):
    cfg = weights.load_config(name)
    shapes = weights.layout(weights.arch_config(cfg))
    n = sum(int(np.prod(s.shape)) for s in shapes.values() if s is not None)
    assert costs.param_count(cfg["arch"]) == n
