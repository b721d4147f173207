"""Bytes the ``pallas`` adapter handed to the device and copied back in
the window, padding included (``core/backends.py::dispatch_stats``:
``bytes_to_device`` + ``bytes_from_device``), per image; nothing where
the program does not count them."""


def read(run):
    from repro.core import backends

    st = backends.dispatch_stats().get("pallas", {})
    if not st.get("bytes_to_device"):
        return None
    return (st["bytes_to_device"] + st["bytes_from_device"]) / run.images
