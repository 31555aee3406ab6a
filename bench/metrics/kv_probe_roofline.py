"""kv_probe_roofline (layer: kernels): the ``kv_probe`` kernel's share of
its HBM roofline.  The least time the chip could take is the bytes the
traced windows' GETs need (``bench.work.kv_probe_bytes`` from the
store's shapes, for each GET the lanes offered in those windows by the
reference's draw: fn 0 in ``ctx["requests"]``), not the empty slots of
the fixed batch the kernel is handed, over the chip's HBM bandwidth;
the share is that over the summed device time of the kernel's calls,
which the trace names by their signature (``bench.work.KV_PROBE_CALL``).
Returns nothing where the trace holds no such call (the store on its
jnp route) or the windows offered no GET."""

from bench import peaks, work

GET = 0


def read(ctx):
    t, sizes = ctx["trace"], ctx["sizes"]
    ns = t.op_ns(0, work.KV_PROBE_CALL.search)
    gets = ctx["requests"].get(GET, 0)
    if not ns or not gets:
        return None
    need = work.kv_probe_bytes(gets, sizes["ways"], 2, 2)
    bound_s = need / peaks.peaks(ctx["device_kind"]).hbm_bytes_per_s
    return 100.0 * bound_s / (ns * 1e-9)
