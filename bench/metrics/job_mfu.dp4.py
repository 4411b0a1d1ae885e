"""job_mfu.dp4: `job_mfu` (`metrics/job_mfu.py`, loaded as it is) in the
four-chip cell: the operations the window's jobs require over their
host seconds, as a share of the four chips' bf16 peak."""
import os

import harness

_JOB_MFU = harness.load_reader(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "job_mfu")


def read(ctx):
    return _JOB_MFU.read(ctx)
