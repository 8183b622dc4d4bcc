"""The reference's posit rounding, written from the format's definition,
agrees with the program's codec on every value in its domain (this test,
and only it, imports the program's codec)."""
import numpy as np
import pytest

from bench.posit_ref import domain_ok, round_to_posit


@pytest.mark.parametrize("n,es", [(16, 2), (8, 2), (16, 1)])
def test_matches_program_codec(n, es):
    from repro.core.convert import f32_to_posit
    from repro.core.decode import decode_to_f32
    from repro.core.types import PositConfig
    cfg = PositConfig(n, es)
    rng = np.random.default_rng(n * 10 + es)
    x = np.concatenate([rng.standard_normal(50000) * s
                        for s in (1e-4, 0.03, 1.0, 50.0, 1e5)]
                       + [np.array([0.0, 1.0, -1.0, 2.0, 0.5, 16.0,
                                    1 + 2 ** -12, 1 + 3 * 2 ** -12],
                                   np.float32)]).astype(np.float32)
    ok = np.asarray(domain_ok(x, n, es))
    got = np.asarray(round_to_posit(x, n, es))[ok]
    want = np.asarray(decode_to_f32(f32_to_posit(x, cfg), cfg))[ok]
    assert ok.mean() > 0.5
    np.testing.assert_array_equal(got, want)


def test_ties_go_to_even():
    # posit16 es=2 near 1 has 11 fraction bits: 1 + 2^-12 is halfway
    # between 1 (even) and 1 + 2^-11 (odd)
    assert float(round_to_posit(np.float32(1 + 2 ** -12), 16, 2)) == 1.0
    assert float(round_to_posit(np.float32(1 + 3 * 2 ** -12), 16, 2)) == \
        1 + 2 ** -10
