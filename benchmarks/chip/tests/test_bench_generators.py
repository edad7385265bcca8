"""The benchmark's copies of the traffic generators give the program's
arrays, at the sizes and the seeds the cells use."""
from __future__ import annotations

import numpy as np
import pytest

import bench_fixture  # noqa: F401  (puts the repo and src on sys.path)

SEEDS = [0, 2**31 + 12345]


@pytest.mark.parametrize("seed", SEEDS)
def test_dvs_copy_equals_program(seed):
    from benchmarks.chip.cell import derive_seed
    from benchmarks.chip.sources import gen_dvs
    from repro.data import dvs

    s = derive_seed(seed, "pool")
    mine, ml = gen_dvs.dvs_moving_edges(4096, 5, (28, 28), band=2,
                                        noise_rate=0.01, seed=s)
    theirs, tl = dvs.dvs_moving_edges(4096, 5, (28, 28), band=2,
                                      noise_rate=0.01, seed=s)
    np.testing.assert_array_equal(ml, tl)
    for a, b in zip(mine, theirs):
        np.testing.assert_array_equal(a, b)
    for a in mine[:64]:
        np.testing.assert_array_equal(gen_dvs.events_to_frames(a, 5, (28, 28)),
                                      dvs.events_to_frames(a, 5, (28, 28)))


@pytest.mark.parametrize("seed", SEEDS)
def test_digits_copy_equals_program(seed):
    from benchmarks.chip.cell import derive_seed
    from benchmarks.chip.sources import gen_digits
    from repro.data.synthetic import synth_digits

    s = derive_seed(seed, "inputs")
    mine, ml = gen_digits.synth_digits(4096, seed=s)
    theirs, tl = synth_digits(4096, seed=s)
    np.testing.assert_array_equal(ml, tl)
    np.testing.assert_array_equal(mine, theirs)


def test_reference_encode_equals_program():
    import jax.numpy as jnp

    from benchmarks.chip.reference import csnn as ref
    from benchmarks.chip.sources import gen_digits
    from repro.core.csnn import CSNNConfig, encode_input

    images, _ = gen_digits.synth_digits(64, seed=3)
    np.testing.assert_array_equal(
        np.asarray(ref.encode_mttfs(jnp.asarray(images), 5)),
        np.asarray(encode_input(jnp.asarray(images), CSNNConfig())))
