"""Seeds 0, 2**31 + 11 and 2**32 + 5 give distinct operands, no overflow."""

import numpy as np

from cellbench import seeds

SEEDS = (0, 5, 2**31 + 11, 2**32 + 5)


def test_context_seeds_are_distinct_and_fit_31_bits():
    got = [seeds.context_seed(s) for s in SEEDS]
    assert len(set(got)) == len(SEEDS)
    assert all(0 <= g < 2**31 for g in got)
    assert got == [seeds.context_seed(s) for s in SEEDS]  # a pure function


def test_data_keys_and_operands_are_distinct():
    import jax

    keys = [np.asarray(jax.random.key_data(seeds.data_key(s, "operand")))
            for s in SEEDS]
    assert len({k.tobytes() for k in keys}) == len(SEEDS)
    # 2**32 + 5 must not collapse onto 5, as jax.random.key would make it
    draws = [np.asarray(jax.random.normal(seeds.data_key(s, "operand"), (8,)))
             for s in SEEDS]
    for i in range(len(SEEDS)):
        for j in range(i):
            assert not np.allclose(draws[i], draws[j])


def test_row_samples_differ_by_seed_and_tag():
    a = seeds.rng(2**32 + 5, "rows.0").choice(1000, 16, replace=False)
    b = seeds.rng(5, "rows.0").choice(1000, 16, replace=False)
    c = seeds.rng(2**32 + 5, "rows.1").choice(1000, 16, replace=False)
    assert not np.array_equal(a, b) and not np.array_equal(a, c)
