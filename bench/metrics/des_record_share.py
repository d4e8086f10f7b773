"""``des_record_share``: the share of the DES calls' candidate request
slots (real lanes, every chunk) that their histograms recorded, over
the traced window, in percent: the ``recorded`` and ``candidates``
arguments of the program's ``memsim.stats`` spans.  The rest fell in
the warm-up, past the population bound, or merged on one
nanosecond."""

from bench.harness import program_spans as ps


def read(run):
    stats = ps.named(ps.of_run(run), "memsim.stats")
    candidates = sum(int(s.args.get("candidates", 0)) for s in stats)
    if candidates <= 0:
        return None
    recorded = sum(int(s.args.get("recorded", 0)) for s in stats)
    return 100.0 * recorded / candidates
