"""The yardstick's counts of work, pinned to the published figures, and
the attention roofline at the shape the work module names."""
import types

import pytest

from chipbench import harness, roofline, trace
from chipbench.kernels import flash_attention, gdm_block
from chipbench.tests.test_trace import DATA


def model(name):
    return harness.load_json(harness.HERE / "configs" / f"{name}.json")["model"]


def test_gdm_dit_is_45_9_gflop_per_sample_step():
    m = model("gdm-dit")
    assert gdm_block.main_term_flops(m) == 12 * (24 * 256 * 768 ** 2
                                                 + 4 * 256 ** 2 * 768)
    assert gdm_block.main_term_flops(m) / 1e9 == pytest.approx(45.9, abs=0.05)
    # the small terms (adaLN, patch in/out, timestep MLP) add under 0.3%
    assert gdm_block.flops_per_sample_step(m) / 1e9 == pytest.approx(
        45.9, rel=3e-3)


def test_dit_xl2_512_is_1_048_tflop_and_the_papers_gmacs():
    m = model("dit-xl2-512")
    assert gdm_block.main_term_flops(m) / 1e12 == pytest.approx(1.048,
                                                                abs=1e-3)
    # arXiv:2212.09748 Table 1: DiT-XL/2 at 512x512 is 524.6 GMACs
    assert gdm_block.flops_per_sample_step(m) / 2e9 == pytest.approx(
        524.6, rel=1e-3)


def test_dit_mfu_counts_live_rows_only():
    from chipbench.metrics import dit_mfu

    class Ctx:
        model = model("dit-xl2-512")
        work = gdm_block
        steps_per_block = 1
        window_s = 2.0
        calls = [{"rows": 5, "bucket": 8}, {"rows": 16, "bucket": 16}]

        def peak(self, key):
            return 197e12

    work = gdm_block.flops_per_sample_step(Ctx.model)
    want = 100.0 * 21 * work / (2.0 * 197e12)
    assert dit_mfu.read(Ctx()) == pytest.approx(want)


def test_flash_attention_counts_scores_and_weighted_sum():
    m = model("dit-xl2-512")
    flops, nbytes = flash_attention.cost(m, gdm_block.attention(m), rows=2)
    assert flops == 2 * 2 * 2 * 16 * 1024 * 1024 * 72
    assert nbytes == 4 * 2 * 1024 * 16 * 72 * 4


# -- the attention's shape, from the work module --------------------------------

CONFIGS = ("gdm-dit", "dit-xl2-512")
BUCKETS = range(1, 57)          # every bucket the fleet8-deep traffic warms
OP_S = 0.25                     # the made-up kernel time of ``op_ctx``


def dit_attention_cost(m, rows):
    """The count before the work module named the attention's shape: DiT
    self-attention over S = latent_hw**2 tokens, one kernel row per
    request row, Q, K, V and O at 4 bytes."""
    s = m["latent_hw"] ** 2
    h, hd = m["num_heads"], m["head_dim"]
    return float(2 * 2 * rows * h * s * s * hd), \
        float(4 * rows * s * h * hd * 4)


def op_ctx(m, buckets, spb=1):
    """A reader's context with one ``flash_attention`` operation of
    ``OP_S`` seconds in the window and one call at each bucket."""
    return types.SimpleNamespace(
        model=m, steps_per_block=spb, lo=0.0, hi=10.0, notes={},
        calls=[{"rows": b, "bucket": b} for b in buckets],
        chip_ops=lambda: [trace.Event("flash_attention", 1.0, 1.0 + OP_S)],
        peak=lambda key: {"bf16_flops_per_s": 197e12,
                          "hbm_bytes_per_s": 819e9}[key])


def test_the_dit_attention_is_self_attention_over_the_latent_tokens():
    m = model("dit-xl2-512")
    assert gdm_block.attention(m) == {"q_len": 1024, "kv_len": 1024,
                                      "rows_per_request": 1,
                                      "calls_per_step": 28}


@pytest.mark.parametrize("name", CONFIGS)
def test_attention_cost_is_the_dit_count_at_every_bucket(name):
    m = model(name)
    shape = gdm_block.attention(m)
    for rows in BUCKETS:
        assert flash_attention.cost(m, shape, rows) \
            == dit_attention_cost(m, rows), rows


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("spb,calls", [(1, 1), (2, 1), (1, 3)])
def test_roofline_share_is_the_dit_formula_at_every_bucket(name, spb,
                                                           calls):
    """At the work module's shape, and at ``calls`` times its calls a
    step: the roofline counts the shape's calls, not the layers."""
    m = model(name)
    shape = gdm_block.attention(m)
    shape = dict(shape, calls_per_step=calls * shape["calls_per_step"])
    per_block = calls * m["num_layers"] * spb
    want = 100.0 * sum(max(per_block * f / 197e12, per_block * b / 819e9)
                       for f, b in (dit_attention_cost(m, r)
                                    for r in BUCKETS)) / OP_S
    got = roofline.share(op_ctx(m, BUCKETS, spb), "flash_attention", shape)
    assert got == want


# flash_attention_roofline on the recorded profile with one call at each of
# buckets 1..56, as the count before the work module named the attention's
# shape read it (the same code at its parent commit, on the CPU)
RECORDED_SHARE = {"gdm-dit": 208964.1206277633,
                  "dit-xl2-512": 3113561.154427367}


@pytest.mark.parametrize("name", CONFIGS)
def test_roofline_share_on_the_recorded_profile_is_unchanged(name):
    if not DATA.exists():
        pytest.skip("no recorded profile")
    conf = {"model": model(name), "work": "gdm_block"}
    ctx = harness.Context(
        profile=trace.load(str(DATA)),
        rec=types.SimpleNamespace(calls=[{"rows": b, "bucket": b}
                                         for b in BUCKETS]),
        cell=types.SimpleNamespace(config=conf),
        device={"kind": "TPU v5 lite"}, before={}, after={}, compiles=0,
        spb=1)
    assert harness.read_metric("flash_attention_roofline", ctx) \
        == RECORDED_SHARE[name]


def test_a_joint_sequence_scales_the_flops_by_its_length_squared():
    """Joint text-and-image attention over 1,024 + 256 tokens, at XL's
    heads: (1280/1024)^2 the FLOPs, 1280/1024 the bytes, and, as both
    shapes are compute-bound on a v5e, (1280/1024)^2 the roofline's
    bound."""
    m = model("dit-xl2-512")
    dit = gdm_block.attention(m)
    joint = dict(dit, q_len=1024 + 256, kv_len=1024 + 256)
    for rows in BUCKETS:
        (f0, b0), (f1, b1) = (flash_attention.cost(m, s, rows)
                              for s in (dit, joint))
        assert f1 / f0 == (1280 / 1024) ** 2 and b1 / b0 == 1280 / 1024
    ctx = op_ctx(m, BUCKETS)
    assert roofline.share(ctx, "flash_attention", joint) / roofline.share(
        ctx, "flash_attention", dit) == pytest.approx(1.5625, rel=1e-12)
    assert "compute-bound" in ctx.notes["flash_attention_roofline"]


def test_guidance_doubles_the_kernel_rows():
    """Classifier-free guidance stacks two kernel rows per request row:
    twice the FLOPs, the bytes and the roofline's bound."""
    m = model("gdm-dit")
    dit = gdm_block.attention(m)
    guided = dict(dit, rows_per_request=2)
    for rows in BUCKETS:
        assert flash_attention.cost(m, guided, rows) == tuple(
            2 * v for v in flash_attention.cost(m, dit, rows))
    ctx = op_ctx(m, BUCKETS)
    assert roofline.share(ctx, "flash_attention", guided) \
        == 2 * roofline.share(ctx, "flash_attention", dit)

