"""The least bytes one fused round's DP must move, and the chip peaks.

The (max,+) DP of a round reads each leaf's option rows once (an int32
spend offset and a value per option, at the real stage and option
counts), writes each leaf's backpointers once (one int32 per stage and
grid point of the leaf's needed grid) and writes each leaf frontier
once.  Pow2 pads, identity stages and the frontier tree are left out, so
this is a lower bound on the traffic and the share it gives against a
measured time cannot pass 100% unless the time is too short.  No
published peak exists for the VPU's (max,+) ops, so the compute bound
is not used; ``dp_ops`` counts the candidate (max,+) evaluations
beside it.
"""

from __future__ import annotations

import json
import os

from bench.cluster import BENCH_DIR


def dp_sizes(fstate) -> dict | None:
    """Bytes and (max,+) candidate count of the last fused round, from
    the resident state's own row signatures; None before a fused round."""
    if fstate is None or fstate.row_sigs is None or fstate.vb_dev is None:
        return None
    vbytes = int(fstate.vb_dev.dtype.itemsize)
    read = written = ops = 0
    for sigs, keys in zip(fstate.row_sigs, fstate.keys_desc):
        stages = [(sig, k) for sig, k in zip(sigs, keys) if sig is not None]
        if not stages:
            continue
        (_, _g_l, tmax_host), mult = stages[0][0]
        nb = tmax_host * mult + 1
        for _sig, k in stages:
            read += len(k) * (4 + vbytes)
            ops += len(k) * nb
        written += len(stages) * nb * 4 + nb * vbytes
    return {"bytes": read + written, "ops": ops}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json") from None
