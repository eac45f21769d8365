"""The control, the reference with its rerank in one bf16 pass put in the
program's place, must fail the comparison the program passes."""
import numpy as np

from chipbench.references import ivf as ref
from chipbench.tests.tiny import built, served


def _numbers(answers, cfg, system, dat, part):
    return ref.compare(answers, 0, dat.queries, dat.base, part,
                       sample=np.arange(len(answers)),
                       **system.stated(cfg))


def _within(nums, limits):
    return all(limits[k] is None or nums[k] <= limits[k] for k in ref.NUMBERS)


def test_program_passes_and_control_fails():
    cfg, system, dat, backend, part = built()
    prog = served(backend, system, cfg, dat.queries)
    nums = _numbers(prog, cfg, system, dat, part)
    limits = system.limits(cfg)
    assert _within(nums, limits), nums
    ctrl = ref.control_answers(prog, dat.queries, dat.base, part,
                               **system.stated(cfg))
    cnums = _numbers(ctrl, cfg, system, dat, part)
    assert not _within(cnums, limits), cnums
    assert cnums["dist_err"] > 10 * limits["dist_err"]
