"""The work a network's convolutions need, counted from their shapes.

Counts depend on the layer list alone, never on how the program tiles
it, so a retiled kernel is read against the same work.  A layer is a
mapping with the keys ``E``, ``F`` (output rows and columns), ``R``,
``S`` (filter size), ``C`` (input channels), ``H``, ``W`` (input rows
and columns), ``M`` (filters) and ``M_live`` (filters that are not
pruned away; the work of a pruned filter is not work).

Per image:

* MACs  = E * F * R * S * C * M_live;
* ops   = 2 * MACs (one multiply and one add);
* bytes = uint8 input activations (H * W * C) + uint8 live filters
  (R * S * C * M_live) + uint8 outputs (E * F * M).
"""
from __future__ import annotations

from typing import Iterable, Mapping


def layer_macs(layer: Mapping[str, int]) -> int:
    return (layer["E"] * layer["F"] * layer["R"] * layer["S"] * layer["C"]
            * layer["M_live"])


def layer_bytes(layer: Mapping[str, int]) -> int:
    return (layer["H"] * layer["W"] * layer["C"]
            + layer["R"] * layer["S"] * layer["C"] * layer["M_live"]
            + layer["E"] * layer["F"] * layer["M"])


def network_work(layers: Iterable[Mapping[str, int]]) -> dict[str, int]:
    """``{"macs", "ops", "bytes"}`` per image, summed over ``layers``."""
    layers = list(layers)
    macs = sum(layer_macs(l) for l in layers)
    return {"macs": macs, "ops": 2 * macs,
            "bytes": sum(layer_bytes(l) for l in layers)}


def roofline_share(work: Mapping[str, int], images: float, seconds: float,
                   ops_per_s: float, bytes_per_s: float) -> tuple[float, str]:
    """Share (in %) of the roofline reached by ``images`` images' work done
    in ``seconds`` of kernel time, and the bound that binds
    (``"compute"`` or ``"memory"``)."""
    t_ops = work["ops"] * images / ops_per_s
    t_bytes = work["bytes"] * images / bytes_per_s
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
