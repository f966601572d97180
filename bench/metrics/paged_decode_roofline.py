"""Share of its roofline that the paged-decode kernel
(``kernels/paged_attn.paged_decode``) reaches in the traced window.

Work: for every decode step of every live lane in the window, the bytes
of the K and V that the query may see (positions up to ``pos``, inside
the window), plus the query and the output (``bench/flops``); the pages
the kernel walks beyond them are not work. At decode the kernel does
about 6 operations per byte, far below the chip's 240, so the byte
bound is the roofline: least time = bytes / HBM bandwidth. The share is
that least time over the kernel's device time.

The kernel's ``pallas_call`` carries no name; in the device trace it is
the ``custom-call`` op whose target is ``tpu_custom_call`` (the only
Mosaic kernel of a dense model's decode step).
"""
from bench import trace


def is_kernel(name: str) -> bool:
    return "tpu_custom_call" in name or trace.short_name(name).endswith(
        "(custom-call)")


def read(r):
    pos = [p for t, p in r.readings.get("decode_pos", [])
           if 0.0 <= t < r.window_s]
    if not pos or not hasattr(r.flops, "decode_attention_bytes"):
        return None
    seconds = sum(trace.op_seconds(r.trace, r.lo, r.hi,
                                   match=is_kernel).values())
    if seconds <= 0:
        return None
    m = r.config["model"]
    need = sum(r.flops.decode_attention_bytes(m, p) for p in pos)
    return 100.0 * need / r.peaks["hbm_bytes_per_s"] / seconds
