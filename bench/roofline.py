"""Peaks and the logical work of one relaxation call.

``relax_work(S, J, z)`` counts what one ``bf_relax`` iteration needs for
S subgraph slabs, J jobs a slab and z vertices a subgraph, with z the
deployment's own (unpadded) subgraph size, so that a kernel that pads
less, or a reimplementation, is read against the same work:

    bytes   read the [S, z, z] f32 adjacency, the [S, J, z] f32 distances
            and spur mask, the [S, J, z] banned-next mask, the [S, J]
            caps; write the [S, J, z] relaxed distances
    ops     one add and one min for each (s, j, u, v): 2 S J z^2

The least time is bytes / HBM bandwidth.  The VPU's peak for f32
min/add is not in the table (no cited figure), so the operations term
is left out; at large J that can understate the least time.
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind):
    """The peak figures of one device kind; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; add them with their source")
    return table[device_kind]


def relax_work(S, J, z):
    """(bytes, ops) of one relaxation iteration over the logical problem."""
    f32 = 4
    bytes_ = f32 * (S * z * z + 4 * S * J * z + S * J)
    return bytes_, 2 * S * J * z * z


def relax_least_s(S, J, z, device_kind):
    """The least time one relaxation iteration can take on the device."""
    bytes_, _ = relax_work(S, J, z)
    return bytes_ / peaks(device_kind)["hbm_bytes_per_s"]
