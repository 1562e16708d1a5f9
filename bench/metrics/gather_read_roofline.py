"""Share of the memory roofline reached by the ``gather_read`` kernel
(``kernels/gather_read.py``) in the traced window: the bytes the audit
gathers must move (``roofline.gather_bytes`` of the accounts its
``read_bulk`` calls returned) over the kernel's device time."""
from roofline import gather_bytes, roofline_share


def read(rec):
    k = (rec.trace or {}).get("kernels", {}).get("gather_read")
    if not k or not rec.chunk_words:
        return None
    return roofline_share(gather_bytes(rec.chunk_words), k["time_s"],
                          rec.device_kind)
