"""Share of speculation steps that verification kept: 1 minus the rounds
that mis-speculated over the speculation steps taken, summed over the
window's requests (``ServeResult.mismatches`` / ``spec_steps``)."""


def read(run):
    steps = sum(r.spec_steps for r in run.result.results)
    if not steps:
        return None
    return 100.0 * (1.0 - sum(r.mismatches for r in run.result.results) / steps)
