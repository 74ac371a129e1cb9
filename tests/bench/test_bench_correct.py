"""What decides ``correct``, driven through a whole run on the CPU at a
small size (the harness's look for a chip skipped): a sound run passes,
its fp8 control put through the same comparison fails, and a timed path
broken underneath fails."""
import pytest
from benchcase import PEAKS, small_cell

from bench import check, run
from bench.faults import FAULTS

# the small cell's limit on the widest gap, set from its readings: sound
# runs read 0.009 to 0.019, the fp8 control 0.075 to 0.12
LIMITS = {"max_logit_gap": 0.04}


def test_sound_run_passes_and_its_control_fails():
    result, reference, sample = run.serve(small_cell(), 21, 0.5, False,
                                          t_start=0.0, device_peaks=PEAKS)
    program = check.compare(reference.served_gaps(sample), LIMITS)
    assert program["ok"], program
    assert program["tokens_compared"]["value"] >= 100
    control = check.compare(reference.control_gaps(sample, "fp8"), LIMITS)
    assert not control["ok"], control
    assert control["tokens_compared"] == program["tokens_compared"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(fault):
    r = run.run_cell(small_cell(), 22, 0.5, False, t_start=0.0,
                     device_peaks=PEAKS, cell_limits=LIMITS,
                     breaker=FAULTS[fault])
    assert not r["correct"], r["compared"]
    assert list(r)[-1] == "compared"
    assert set(r["compared"]) == {"tokens_compared", "max_logit_gap"}
