"""PR 34, call 5, as it ran, for the record: benchmark/run.py with the
partitioned trainer's fences placed as FENCES says.

    python3 <path>/pr34_fences.py FENCES <run.py args>

from the root of a checkout; FENCES ``at:unit,unit;at:`` (``0:;2:3``:
behind ``u00`` the cotangent alone, behind ``u02`` with ``u03``'s
kernel; ``0:10;6:12`` is what call 5's tree did for AlexNet). It
patches ``DataParallelTrainer._backward_fences``, which the tree of
call 5 had (``{unit: [units]}``: fenced behind that unit's output,
with those later units' weights) and the final tree folded into the
one fence of ``_forward_range``: against the final tree it changes
nothing."""
import os
import runpy
import sys

fences = {}
for part in sys.argv[1].split(";"):
    at, _, units = part.partition(":")
    fences[int(at)] = [int(u) for u in units.split(",") if u]
sys.argv = ["benchmark/run.py"] + sys.argv[2:]
sys.path.insert(0, os.getcwd())
from veles_tpu.parallel import dp  # noqa: E402

dp.DataParallelTrainer._backward_fences = \
    lambda self, params_list: dict(fences)
runpy.run_path("benchmark/run.py", run_name="__main__")
