"""Hand counts of the FLOP and byte counters, and the peak table."""
import json
from pathlib import Path

import pytest

from chipbench import flops
from chipbench.peaks import peaks

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_granite_train_flops_per_token():
    # 2 x (2 layers x 199.2M + 201.3M head) + causal-half attention
    # (2 x 4096 x 32 x 128 x 2 layers), times 3 for a training step
    got = flops.train_flops_per_token(config("granite-3-8b"), 4096)
    assert got / 1e9 == pytest.approx(3.80, abs=0.005)


def test_granite_moe_train_flops_per_token():
    # 10 layers of attention (3.1M) + 8 of 32 experts (12.6M) + router,
    # the 50.3M head, and attention over 4096 / 2 positions
    got = flops.train_flops_per_token(config("granite-moe-1b-a400m"), 4096)
    assert got / 1e9 == pytest.approx(1.50, abs=0.005)


def test_granite_decode_step_bytes():
    # 599.8M bf16 weights (1.20 GB) + K and V of 2 layers x 32 x 2304 x 8 x 128
    # in bf16 (0.60 GB)
    got = flops.decode_bytes(config("granite-3-8b"), 32, 2304)
    assert got / 1e9 == pytest.approx(1.80, abs=0.005)


def test_prefill_counts_the_head_once_per_sequence():
    c = config("granite-3-8b")
    with_head = flops.prefill_flops(c, 1, 2048)
    layers = 2048 * flops.forward_flops_per_token(c, 1024, head=False)
    assert with_head - layers == pytest.approx(2 * flops.head_params(c))


def test_v5e_peaks_and_unknown_kind():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks("_source")


def test_peak_table_names_its_source():
    from chipbench import peaks as mod
    table = json.loads(mod._TABLE.read_text())
    assert "Google Cloud" in table["_source"]
