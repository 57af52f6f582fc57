"""Seconds of device SELF time, in the profiled check, of the operations
under the ``sr.grow`` scope: what a growth event runs ON the device since
PR 48 - the queue's live window slid into buffers of the new allocation
(``_slide_queue``) and the table's buckets split in place
(``ops/buckets.bucket_split``).  ``growth_s`` is the host's share of the
same events.  From the trace's event metadata (srbench/xstages.py): a stage
like the step's own, so it adds up with them to the device's busy time; 0
where nothing grew."""

UNIT = "s"
LAYER = "kernels"
MOVES = "check_s"
SOURCE = "device_trace"


def read(ctx):
    from srbench import xstages

    return xstages.stage_seconds(ctx, __file__, "sr.grow")
