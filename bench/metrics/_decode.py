"""What the decode metrics share: the steps of the traced calls and the
device time of the engine's jitted decode step."""

STEP_MODULE = "jit_decode_step"


def traced_steps(run):
    """(flops, least bytes) of every decode step of the traced calls, by
    the configuration's count module: a call of prompt P and n new tokens
    runs P + n steps at positions 0 .. P + n - 1."""
    B = run.counters["batch"]
    out = []
    for P, n in run.extra["traced_calls"]:
        for pos in range(P + n):
            out.append((run.counts.decode_flops(run.arch, B, pos),
                        run.counts.decode_bytes(run.arch, B, pos)))
    return out


def step_device(run):
    """(seconds, count) of the decode step's programs in the trace."""
    secs = sum(s for name, s in run.trace.module_s.items()
               if name.startswith(STEP_MODULE))
    n = sum(c for name, c in run.trace.module_count.items()
            if name.startswith(STEP_MODULE))
    return secs, n
