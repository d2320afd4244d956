"""A run with its timed path broken underneath comes out not correct.

The harness runs on the CPU here (its look for a chip skipped) at a small
size, with the real traffic and check; the fault wraps each service's
block call.  One sound run beside them comes out correct.  A one-chip
cell has no exchange between chips to leave out.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.tests import small


def unchanged(runner):
    """The block call returns its state as it came in."""
    def run(params, latent, prompt, idx):
        lat = jnp.asarray(latent)
        return lat, lat
    return run


def half_left_out(runner):
    """Only the first half of the batch is computed; the rest comes back
    as it went in."""
    def run(params, latent, prompt, idx):
        lat, x0 = runner(params, latent, prompt, idx)
        keep = np.arange(len(latent))[:, None, None] < max(len(latent) // 2, 1)
        return (jnp.where(keep, lat, jnp.asarray(latent)),
                jnp.where(keep, x0, jnp.asarray(latent)))
    return run


def answer_altered(runner):
    """One row's answer is altered where it is produced."""
    def run(params, latent, prompt, idx):
        lat, x0 = runner(params, latent, prompt, idx)
        return lat.at[0].multiply(1.02), x0.at[0].multiply(1.02)
    return run


@pytest.fixture(scope="module")
def cell():
    return small.cell()


def test_sound_run_is_correct(cell):
    line = small.run(cell, 2**31 + 5)
    assert line["correct"] is True
    assert list(line)[-1] == "check"
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("fault", [unchanged, half_left_out, answer_altered])
def test_fault_is_not_correct(cell, fault):
    line = small.run(cell, 2**31 + 5, fault=fault)
    assert line["correct"] is False
    assert any(v["value"] > v["limit"] for v in line["check"].values())
