"""``des_lent_share``: the share of the harvesting DES calls' candidate
request slots whose arrival fell in a lent window, over the traced
window, in percent: the ``lent`` and ``candidates`` arguments of the
program's ``memsim.stats`` spans, for the calls whose
``memsim.simulate`` span says they ran the harvest pass."""

from bench.harness import harvest_pass
from bench.harness import program_spans as ps


def read(run):
    calls = {s.args.get("call") for s in harvest_pass.harvesting_calls(run)}
    stats = [s for s in ps.named(ps.of_run(run), "memsim.stats")
             if s.args.get("call") in calls and "lent" in s.args]
    candidates = sum(int(s.args.get("candidates", 0)) for s in stats)
    if candidates <= 0:
        return None
    return 100.0 * sum(int(s.args["lent"]) for s in stats) / candidates
