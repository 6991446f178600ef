"""Combined-axes proof on the 8-device virtual CPU mesh: ONE jitted
train step over dp x pp x cp x tp simultaneously with a Switch-MoE layer
in the stack (ep over "dp"), parity vs a single device — the same case
``dryrun_multichip`` runs."""

import pytest

pytestmark = pytest.mark.slow


def test_combined_axes_train_step():
    import __graft_entry__ as ge

    ge._dryrun_combined(8)
