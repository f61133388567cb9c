"""The multi-device cell's figures of the chip-to-chip copies, from the
program's own counters over the window; ``params["key"]`` says which.

A chip module counts the copies it makes of other chips' tiles
(``remote_copies``, ``remote_bytes_in``) and the reads a copy already
there served (``remote_hits``). The driver leaves their differences over
the window in the dict its ``setup()`` returned, as ``program_counters``
(``<counter>`` summed over the modules, ``<counter>.<module>`` for each),
and under ``multidev`` the least bytes one step has to copy
(``ops_multidev.potrf_min_remote_bytes``).

``remote_gib_per_step``: bytes copied / steps attempted, in GiB.
``remote_bytes_over_min``: bytes copied / (the least x steps).
``remote_reuse_share``: hits / (hits + copies), in percent.
``ici_peak_share``: the bytes a step that crossed into the chip that
received most / the traced step time / the published interchip bandwidth
(``peaks.json`` ``ici_bits_per_s`` / 8), in percent.

``None`` where there is nothing to read: a program without the counters,
a driver that leaves none, no step, a run without a device trace or a
device without a published link (``ici_peak_share``).
"""


def read(record, params):
    counts = record["setup"].get("program_counters") or {}
    facts = record["setup"].get("multidev") or {}
    steps = record["window"]["attempted"] - record["window"]["failed"]
    if "remote_bytes_in" not in counts or steps <= 0:
        return None
    copied = counts["remote_bytes_in"]
    key = params["key"]
    if key == "remote_gib_per_step":
        return copied / steps / 2 ** 30
    if key == "remote_bytes_over_min":
        least = facts.get("min_remote_bytes_per_step")
        return copied / (least * steps) if least else None
    if key == "remote_reuse_share":
        reads = counts.get("remote_hits", 0) + counts.get("remote_copies", 0)
        return 100.0 * counts.get("remote_hits", 0) / reads if reads else None
    if key == "ici_peak_share":
        trace, peaks = record["trace"], record["peaks"] or {}
        into = [n for name, n in counts.items()
                if name.startswith("remote_bytes_in.")]
        if not trace.get("steps") or not into or \
                not peaks.get("ici_bits_per_s"):
            return None
        step_s = trace["window_s"] / trace["steps"]
        return 100.0 * max(into) / steps / step_s / \
            (peaks["ici_bits_per_s"] / 8.0)
    raise ValueError(f"key={key!r} is none of this reader's")
