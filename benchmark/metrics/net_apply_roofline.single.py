"""Share of the HBM roofline reached by the Beneš network passes of the
single-source searches (``ops/relay_pallas.py``): the Pallas kernels whose
result is the network's bit vector, ``net_size / 32`` words, each moving
its HBM-resident operands and result once (``roofline.py``), against
their device time in the trace."""

from benchmark.roofline import pallas_call, roofline_share


def read(run):
    if run.trace is None or run.facts.get("batch") != 1 or not run.peaks:
        return None
    words = run.layout["net_size"] // 32

    def is_net_pass(hlo):
        call = pallas_call(hlo) if "tpu_custom_call" in hlo else None
        return call is not None and call[0] == words

    return roofline_share(run.trace.op_events(is_net_pass),
                          run.peaks["hbm_bytes_per_s"])
