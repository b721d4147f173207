"""Native ``pallas`` dispatches (``core/backends.py::dispatch_stats``)
in the window, per image; nothing where the backend did not run."""


def read(run):
    return run.kernel_calls / run.images if run.kernel_calls else None
