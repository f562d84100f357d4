"""``Plan.execute``'s optimise and finalise of the last compute, which run
before the compute's start event and so inside ``plan_s``: what of ``plan_s``
is the plan, the rest being the Array-API calls that build the expression."""

METRICS = [
    {"name": "plan_finalize_s", "unit": "s", "better": "lower", "source": "program_counter",
     "layer": "expression and plan", "moves": "compute_s"},
]


def read(traced):
    return traced.stats.get("plan_finalize_s")
