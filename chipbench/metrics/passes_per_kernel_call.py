"""Plan passes per native ``pallas`` call in the window
(``core/backends.py::dispatch_stats``: ``passes`` over ``native``): 1
where every plan tile is its own device call, a layer's tiles where one
call serves a layer; nothing where the program does not count passes."""


def read(run):
    from repro.core import backends

    st = backends.dispatch_stats().get("pallas", {})
    if not st.get("passes") or not st.get("native"):
        return None
    return st["passes"] / st["native"]
