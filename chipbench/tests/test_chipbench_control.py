"""The control comes out not correct: the reference put in the program's
place one precision down (fp8 where the program holds bf16) reads a
widest gap above the limit on the same sample on which the program reads
below it.  On the CPU at reduced widths, with the reduced cell's limits
(chipbench/tests/tiny.py); the cells' own readings are taken on the chip
by chipbench/calibrate.py."""

import pytest

from chipbench import calibrate
from chipbench.tests import tiny
from chipbench.tests.tiny import quiet_cache  # noqa: F401  (fixture)


@pytest.mark.parametrize("arch,kind", [("minitron-4b", "backlog"),
                                       ("zamba2-1.2b", "chat")])
def test_control_is_not_correct(quiet_cache, arch, kind):  # noqa: F811
    row = calibrate.reading(tiny.spec(arch, kind), 1, 3.0, True,
                            peaks=tiny.PEAKS, require_tpu=False)
    limit = tiny.LIMIT[arch]
    assert row["tokens"] > 0 and row["wrong_length_or_id"] == 0
    assert row["program_gap"] <= limit and row["program_correct"]
    assert row["control_gap"] > limit and not row["control_correct"]
