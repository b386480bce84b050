"""The benchmark's own arithmetic: bytes and FLOPs a step needs, the
peaks table, percentiles and the traffic generator."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import costs, traffic
from chipbench.peaks import peaks_for
from chipbench.stats import percentile

CONFIGS = Path(__file__).resolve().parents[2] / "chipbench" / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


#: a GQA decoder at minitron-4b's published widths, with its ungated
#: two-matrix MLP: the arithmetic for a cell that no file holds yet
MINITRON = {"n_layers": 32, "d_model": 3072, "n_heads": 24, "n_kv_heads": 8,
            "head_dim": 128, "d_ff": 9216, "vocab": 256000, "act": "relu2",
            "dtype": "bfloat16"}


def test_phi3_decode_weight_bytes_by_hand():
    # per block: q, k, v, o 4 * 3072 * 3072 = 37748736, MLP 3 * 3072 *
    # 8192 = 75497472, two norms 6144; 32 blocks, the final norm 3072 and
    # the head over the real vocabulary 3072 * 32064 = 98500608; bf16
    per_block = 37748736 + 75497472 + 6144
    params = 32 * per_block + 3072 + 98500608
    assert params == 3722578944
    assert costs.decode_weight_bytes(_cfg("phi3-mini-3.8b")) == 2 * params
    assert costs.decode_weight_bytes(_cfg("phi3-mini-3.8b")) == 7445157888


def test_minitron_decode_weight_bytes_by_hand():
    # q and o 3072 * 3072 each, k and v 3072 * 1024 each (8 kv heads of
    # 128), an ungated MLP 2 * 3072 * 9216, the head 3072 * 256000
    per_block = 2 * 9437184 + 2 * 3145728 + 56623104 + 6144
    params = 32 * per_block + 3072 + 786432000
    assert costs.decode_weight_bytes(MINITRON) == 2 * params
    assert round(2 * params / 1e9, 2) == 6.81


def test_kv_bytes_and_decode_step_bytes():
    phi3, mini = _cfg("phi3-mini-3.8b"), MINITRON
    assert costs.kv_bytes_per_position(phi3) == 2 * 32 * 32 * 96 * 2 == 393216
    assert costs.kv_bytes_per_position(mini) == 2 * 32 * 8 * 128 * 2 == 131072
    # slots writing at positions 0 and 10 read 1 and 11 valid positions
    # and write one each
    assert costs.decode_step_bytes(phi3, [0, 10]) == (
        7445157888 + 393216 * (1 + 1 + 11 + 1))
    assert costs.decode_step_bytes(phi3, []) == 7445157888


def test_token_and_request_flops_by_hand():
    mini = MINITRON
    matmul = 32 * (25165824 + 56623104)
    attn_at_0 = 4 * 32 * 24 * 128 * 1
    assert costs.token_flops(mini, 0, False) == 2 * matmul + attn_at_0
    assert costs.token_flops(mini, 0, True) == (
        2 * matmul + attn_at_0 + 2 * 786432000)
    # a 3-token prompt: positions 0 and 1 through the blocks, then tokens
    # decoded at positions 2 and 3 with the head
    want = (costs.token_flops(mini, 0, False) + costs.token_flops(mini, 1, False)
            + costs.token_flops(mini, 2, True) + costs.token_flops(mini, 3, True))
    assert costs.request_flops(mini, 3, 0, 2, prefill=True) == want


def test_peaks_known_and_unknown_kind():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        peaks_for("TPU v9 imaginary")


def test_percentile_over_every_sample():
    values = list(range(1, 101))
    assert percentile(values, 50) == pytest.approx(50.5)
    assert percentile(values, 95) == pytest.approx(95.05)
    # one far sample in a thousand moves the maximum, not the p95
    assert percentile([1.0] * 999 + [1e9], 95) == 1.0
    with pytest.raises(ValueError):
        percentile([], 95)


MIX = {"driver": "open_loop", "schedule_seed": 0, "rate_rps": 4.0,
       "drain_s": 5,
       "prompt_len": {"dist": "lognormal", "median": 128, "sigma": 1.0,
                      "min": 16, "max": 896},
       "output_len": {"dist": "lognormal", "median": 32, "sigma": 0.7,
                      "min": 8, "max": 127}}


def test_traffic_same_seed_same_requests_other_seed_same_schedule():
    a = traffic.generate(MIX, 2**31 + 7, 20.0, 32064, 1024)
    b = traffic.generate(MIX, 2**31 + 7, 20.0, 32064, 1024)
    c = traffic.generate(MIX, 5, 20.0, 32064, 1024)
    assert [(o.due_s, o.prompt, o.max_new) for o in a] == \
        [(o.due_s, o.prompt, o.max_new) for o in b]
    win_a = [o for o in a if o.counted]
    win_c = [o for o in c if o.counted]
    assert len(win_a) == len(win_c) == 80
    assert sorted(len(o.prompt) for o in win_a) == \
        sorted(len(o.prompt) for o in win_c)
    # another seed: the same schedule of sizes and due times, other ids
    assert [(o.due_s, len(o.prompt), o.max_new) for o in a] == \
        [(o.due_s, len(o.prompt), o.max_new) for o in c]
    assert [o.prompt for o in a] != [o.prompt for o in c]
    # another schedule seed: the same sizes in another order
    d = traffic.generate(dict(MIX, schedule_seed=1), 5, 20.0, 32064, 1024)
    win_d = [o for o in d if o.counted]
    assert sorted(len(o.prompt) for o in win_d) == \
        sorted(len(o.prompt) for o in win_c)
    assert [len(o.prompt) for o in win_d] != [len(o.prompt) for o in win_c]
    assert all(0 <= o.due_s <= 20.0 for o in win_a)
    assert all(o.due_s > 20.0 for o in a if not o.counted)
    assert all(16 <= len(o.prompt) <= 896 and 8 <= o.max_new <= 127
               for o in a)
    assert np.all(np.diff([o.due_s for o in a]) >= 0)


def test_traffic_backlog_and_cache_fit():
    mix = dict(MIX, driver="backlog", requests=50)
    reqs = traffic.generate(mix, 1, 20.0, 100, 1024)
    assert len(reqs) == 50 and all(o.due_s == 0 and o.counted for o in reqs)
    with pytest.raises(ValueError, match="does not fit"):
        traffic.generate(MIX, 1, 20.0, 100, 1000)
