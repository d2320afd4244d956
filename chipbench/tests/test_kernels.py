"""The yardstick's counts of work, pinned to the published figures."""
import pytest

from chipbench import harness
from chipbench.kernels import flash_attention, gdm_block


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
    flops, nbytes = flash_attention.cost(m, rows=2)
    assert flops == 2 * 2 * 2 * 16 * 1024 * 1024 * 72
    assert nbytes == 4 * 2 * 1024 * 16 * 72 * 4
