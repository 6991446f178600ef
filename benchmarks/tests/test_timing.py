"""The boundary arithmetic: PR 22's failure as a test."""

import pytest

import timing

LUMP_S, LUMP_TOKENS = 0.82, 256     # one harvest window of 32 slots x 8


def _boundaries(offset: float, until: float):
    """A server that commits LUMP_TOKENS every LUMP_S, first at
    ``offset`` after time 0."""
    out, t, n = [], offset, 0
    while t <= until:
        n += LUMP_TOKENS
        out.append((t, n))
        t += LUMP_S
    return out


@pytest.mark.parametrize("offset", [0.0, 0.1, 0.3, 0.41, 0.6, 0.8199])
def test_rate_does_not_depend_on_the_window_offset(offset):
    seconds = 30.0
    owned = timing.window_boundaries(_boundaries(offset, 40.0), 1.0, seconds)
    assert timing.rate_between(owned) == pytest.approx(
        LUMP_TOKENS / LUMP_S, rel=1e-12)
    # the window closes on the first boundary at or after its nominal end
    assert owned[-1][0] >= 1.0 + seconds > owned[-2][0]


def test_counting_lumps_in_a_nominal_window_does_depend_on_it():
    """What PR 22 did: lumps inside [open, open + seconds) over seconds."""
    seconds, rates = 30.0, set()
    for offset in (0.0, 0.3, 0.6):
        inside = [b for b in _boundaries(offset, 40.0)
                  if 1.0 <= b[0] < 1.0 + seconds]
        rates.add(round(len(inside) * LUMP_TOKENS / seconds, 6))
    assert len(rates) > 1
    assert (max(rates) - min(rates)) / min(rates) > 0.02   # a lump is 2.7 %


def test_rate_needs_two_boundaries():
    assert timing.rate_between([]) is None
    assert timing.rate_between([(1.0, 5)]) is None
    assert timing.rate_between([(1.0, 5), (3.0, 25)]) == 10.0


def test_percentile_interpolates():
    xs = list(range(1, 11))
    assert timing.percentile(xs, 50) == 5.5
    assert timing.percentile(xs, 90) == pytest.approx(9.1)
    assert timing.percentile([], 50) is None
    assert timing.percentile([7.0], 90) == 7.0


def test_time_weighted_mean_and_spread():
    # 2 slots for 1 s, then 4 slots for 3 s
    assert timing.time_weighted_mean(
        [(0.0, 0), (1.0, 2), (4.0, 4)]) == pytest.approx(3.5)
    assert timing.quartile_spread([10, 10, 10, 10]) == 0.0
    assert timing.quartile_spread([9.0, 10.0, 11.0]) == pytest.approx(0.2)
