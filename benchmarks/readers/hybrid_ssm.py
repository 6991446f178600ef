"""Readers the hybrid state-space cell adds: a scope's share of ITS
roofline (``rooflines_hybrid_ssm``), in the decode program (device time
by ``readers.latent_moe``: any depth, self time, matched by ``op_name``)
or in the chunk program (``readers.hyper_latent``: each run read against
its own extent's compiled text), and the share of prefill chunks that
started from a carried state, from the batcher's own spans.  A program
without the scope, a run without the counters, or a trace without the
spans, gives nothing."""

import rooflines
import rooflines_hybrid_ssm
from readers import hyper_latent, latent_moe, program_spans


def _share(ms, counters, params, run):
    if not ms:
        return None
    flops, nbytes = rooflines_hybrid_ssm.KERNELS[params["kernel"]](
        counters, run.config)
    least = rooflines.least_seconds(
        flops, nbytes, run.devices[0].device_kind)
    return 100.0 * least / (ms / 1e3)


def decode_roofline(trace, counters, params, run):
    """The least time the chip could take for what the scope's work
    needs in one decode step (of the step's own counters) over the time
    the scope took, in percent."""
    if "decode_steps_counted" not in counters:
        return None
    return _share(latent_moe._scope_ms(trace, params, run)[0], counters,
                  params, run)


def chunk_roofline(trace, counters, params, run):
    """The same for one run of the chunk program (``chunk_tokens``)."""
    if "chunk_tokens" not in counters:
        return None
    return _share(hyper_latent._scope_ms(trace, params, run), counters,
                  params, run)


def chunks_carried_share(trace, counters, params, run):
    """Percent of the traced ``tlm.serve.dispatch_prefill`` spans whose
    ``ssm_state_in`` says the chunk started from the slot's carried state
    (not from zeros, as a prompt's first chunk does)."""
    path = run.tracer.xplane()
    if not path:
        return None
    said = []
    for name, _, _, stats in program_spans.load_spans(path):
        value = stats.get("ssm_state_in")
        if name == program_spans.PREFIX + "dispatch_prefill" \
                and value is not None:
            said.append(value.decode() if isinstance(value, bytes)
                        else str(value))
    if not said:
        return None
    return 100.0 * sum(v == "carried" for v in said) / len(said)
