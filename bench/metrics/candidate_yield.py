"""Designs the sampler returned per design it validated, in percent:
items of `candidates` over items of `candidates.validate`, from the
program's spans. Rounds topped up past the request are the loss."""
from bench.program_spans import summaries, total


def read(run):
    tels = summaries(run)
    if tels is None:
        return None
    _, _, used = total(tels, "candidates")
    _, _, validated = total(tels, "candidates.validate")
    return 100.0 * used / validated if validated else None
