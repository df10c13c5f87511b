"""Shared arithmetic of the ``*_roofline`` readers: the least time the
chip could take for a count of operations and bytes, by the published
peaks (``peaks.json``), over the kernel's device time."""


def share(ops, nbytes, ops_peak, hbm_bw, device_s):
    """``(share %, {"bound": "compute"|"memory"})``, or None without a
    device time to divide by."""
    if not device_s:
        return None
    t_ops, t_mem = ops / ops_peak, nbytes / hbm_bw
    return (100.0 * max(t_ops, t_mem) / device_s,
            {"bound": "compute" if t_ops >= t_mem else "memory"})
