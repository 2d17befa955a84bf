"""Device time of the prefill programs (``XLA Modules`` named
``jit_prefill...``: whole-prompt, paged, prefix/chunk) / traced span."""

from benchmarks.harness.program_spans import module_share


def read(run):
    if run.trace is None:
        return None
    share = module_share(run.trace, "jit_prefill")
    return None if share is None else 100.0 * share
