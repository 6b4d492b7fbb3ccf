"""Stream step: device time inside the scheduler's bucket executables
(``make_bucket_step``'s jitted ``bucket``), from the trace's ``XLA Modules``
line: the mean over the whole steps of the traced span, each step weighed by
the frames it carried.  So it is the device time of the step a frame rode
in, mean over the span's frames: with one session every step carries one
frame and it is the plain mean; where a span holds steps of more than one
bucket size, a k=4 step counts four times a k=1 step's weight."""

from .bucket_steps import steps_with_riders


def read(ctx):
    steps = steps_with_riders(ctx)
    frames = sum(r for _, r in steps)
    return 1e3 * sum(t * r for t, r in steps) / frames if frames else None
