"""Share of the memory roofline reached by the ``commit_fused`` kernel
(``kernels/commit_fused.py``) in the traced window: the bytes the
committed transfers' writes must move (``roofline.commit_bytes`` of two
accounts per transfer) over the kernel's device time."""
from roofline import commit_bytes, roofline_share


def read(rec):
    k = (rec.trace or {}).get("kernels", {}).get("commit_fused")
    written = 2 * int(rec.transfers["t_end"].shape[0])
    if not k or not written:
        return None
    return roofline_share(commit_bytes(written), k["time_s"],
                          rec.device_kind)
