"""The reader of the two counts a ``tlm.serve.dispatch_prefill`` span
carries where the chunk function knows what its positions leave of its
attention (``k_blocks_run``, ``k_blocks_extent``: key blocks the chunk's
flash calls compute / key blocks of the contexts they read, by the
kernel's own bounds; ``apex_tpu/serving/serve.py:_prefill_span``).  The
spans are the program's; ``readers/program_spans.load_spans`` finds them
in the trace."""

from readers import program_spans


def run_share(trace, counters, params, run):
    """Percent of the key blocks of the contexts read that the chunks'
    flash calls computed, over the traced stretch's ``dispatch_prefill``
    spans: 100 where positions bound nothing.  None where no span
    carries the counts (a program before them, a chunk function without
    ``k_blocks``, a stretch with no prefill)."""
    if not hasattr(run, "flash_block_spans"):
        path = run.tracer.xplane()
        run.flash_block_spans = program_spans.nest(
            program_spans.load_spans(path)) if path else []
    spans = [s for s in run.flash_block_spans
             if s.name == "dispatch_prefill" and "k_blocks_extent" in s.stats]
    if trace is not None and trace.host_spans:
        spans = [s for s in spans
                 if s.start >= trace.t0 and s.end <= trace.t1]
    extent = sum(int(s.stats["k_blocks_extent"]) for s in spans)
    if not extent:
        return None
    return 100.0 * sum(int(s.stats["k_blocks_run"]) for s in spans) / extent
