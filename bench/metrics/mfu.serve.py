"""The whole serve step's share of the chip's peak: the model FLOPs of
every prefill and decode call in the traced stretch
(``bench.costs.DenseDecoder``), over the stretch's host time, over
the bf16 peak."""


def read(run):
    if run.trace is None:
        return None
    c = run.system.costs
    flops = sum(c.prefill_flops(n) if kind == "prefill" else
                c.decode_flops(n)
                for kind, a, b, n in run.system.calls
                if run.tracer.covers(a, b))
    window = run.tracer.t_stop - run.tracer.t_start
    return 100.0 * flops / window / run.peak["bf16_flops_per_s"]
