"""The work counts against hand counts at two shapes."""
import pytest

from bench import flops, harness

SMOLLM = harness.published_sizes(
    harness.load_json(harness.BENCH / "configs" / "smollm-135m.json"))
QWEN3 = harness.published_sizes(
    harness.load_json(harness.BENCH / "configs" / "qwen3-0.6b.json"))


def test_matmul_params_by_hand():
    # smollm: 30 x (576*64*(2*9 + 2*3) + 3*576*1536) + 49152*576
    assert flops.matmul_params(SMOLLM) == 30 * (884_736 + 2_654_208) \
        + 28_311_552 == 134_479_872
    # qwen3: 28 x (1024*128*(2*16 + 2*8) + 3*1024*3072) + 151936*1024
    assert flops.matmul_params(QWEN3) == 28 * (6_291_456 + 9_437_184) \
        + 155_582_464 == 595_984_384


def test_parameter_count_agrees_with_program():
    for cfg in ("smollm-135m", "qwen3-0.6b"):
        c = harness.load_json(harness.BENCH / "configs" / f"{cfg}.json")
        m = harness.published_sizes(c)
        assert flops.matmul_params(m) + flops.norm_params(m) == \
            harness.program_model(c).param_count()


def test_train_flops_per_token_by_hand():
    # 6 N + 6 L S H hd
    assert flops.train_flops_per_token(SMOLLM, 4096) == \
        6 * 134_479_872 + 6 * 30 * 4096 * 9 * 64 == 1_231_552_512
    assert flops.train_flops_per_token(SMOLLM, 1024) == \
        6 * 134_479_872 + 6 * 30 * 1024 * 9 * 64


def test_decode_step_by_hand():
    B, live = 32, 1153
    weights = (595_984_384 + 28 * (2 * 1024 + 2 * 128) + 1024) * 2
    kv = B * (2 * 28 * 8 * 128 * 2) * (live + 1)
    logits = B * 151_936 * 4
    assert flops.decode_step_bytes(QWEN3, B, live) == weights + kv + logits
    assert flops.decode_step_flops(QWEN3, B, live) == \
        B * (2 * 595_984_384 + 4 * 28 * 16 * 128 * live)
    t, bound = flops.least_seconds(flops.decode_step_flops(QWEN3, B, live),
                                   flops.decode_step_bytes(QWEN3, B, live),
                                   harness.peak_for("TPU v5 lite"))
    assert bound == "bytes" and t == pytest.approx(
        (weights + kv + logits) / 819e9)
