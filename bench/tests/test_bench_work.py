"""Work counts: routed rows and touched experts, never padded capacity
or all-expert sweeps; the roofline reader against a hand count."""
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import readers, work

M = {"reference": "mixtral", "hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
     "num_key_value_heads": 1, "head_dim": 4, "num_local_experts": 8,
     "num_experts_per_tok": 2, "vocab_size": 32, "num_hidden_layers": 2}
PEAK = {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e3}


def test_expert_ffn_counts_rows_and_touched_experts():
    f, b = work.expert_ffn_call(M, rows=3, touched=2)
    assert f == 6 * 8 * 16 * 3
    # two touched experts' three matrices + 3 rows in and out, bf16
    assert b == 2 * (3 * 8 * 16 * 2 + 2 * 8 * 3)
    # a capacity bucket padded to 16 rows, or all 8 experts swept, is not
    # work the layer needs
    assert work.expert_ffn_call(M, 3, 2)[0] < work.expert_ffn_call(
        M, 16, 2)[0]
    assert work.expert_ffn_call(M, 3, 2)[1] < work.expert_ffn_call(
        M, 3, 8)[1]


def test_least_time_names_its_bound():
    assert work.least_time(4e3, 1e3, PEAK) == (4.0, "compute")
    assert work.least_time(1e3, 4e3, PEAK) == (4.0, "memory")


def test_token_and_prefill_flops():
    d, f, H, Hkv, hd, E, K, V = 8, 16, 2, 1, 4, 8, 2, 32
    per_layer = lambda c: (2 * d * (H * hd + 2 * Hkv * hd) + 2 * H * hd * d
                           + 4 * H * hd * c + 2 * d * E + K * 6 * d * f)
    assert work.token_flops(M, 5) == 2 * per_layer(5) + 2 * d * V
    assert work.token_flops(M, 5, logits=False) == 2 * per_layer(5)
    # a prompt: token i attends i + 1 keys, logits at the last one only
    want = sum(work.token_flops(M, i + 1, logits=False) for i in range(7))
    assert work.prefill_flops(M, 7) == want + 2 * d * V


def _ctx(adm, steps, kernel_s):
    tr = NS(kernel_seconds=lambda pat: (kernel_s, 1))
    return NS(trace=tr, m=M, peak=PEAK, adm_log=adm, step_log=steps,
              in_window=lambda t: 0 < t <= 10)


def test_roofline_counts_live_rows_and_the_steps_touched_experts():
    # one admission of 3 tokens (6 rows, min(8, 6) experts), one decode
    # step with 2 live slots whose rows touch 1 expert in layer 0 and 3
    # in layer 1
    touched = np.zeros((2, 8), bool)
    touched[0, 0] = touched[1, :3] = True
    ctx = _ctx([(1, 2, 3)], [[3, 4, 2, touched]], kernel_s=100.0)
    need = 2 * work.least_time(*work.expert_ffn_call(M, 6, 6), PEAK)[0]
    need += work.least_time(*work.expert_ffn_call(M, 4, 1), PEAK)[0]
    need += work.least_time(*work.expert_ffn_call(M, 4, 3), PEAK)[0]
    assert readers.expert_ffn_roofline(ctx) == \
        pytest.approx(100 * need / 100.0)
    # a traced step without its routing telemetry reads nothing
    ctx.step_log[0][3] = None
    assert readers.expert_ffn_roofline(ctx) is None


def test_roofline_reads_nothing_without_kernel_events():
    ctx = _ctx([(1, 2, 3)], [], kernel_s=0.0)
    ctx.trace = NS(kernel_seconds=lambda pat: (0.0, 0))
    assert readers.expert_ffn_roofline(ctx) is None
