"""Host time from the start of a compute (the first Array-API call that
builds the expression) to the entry of ``JaxExecutor.execute_dag``:
expression building, plan construction, optimisation and finalisation."""

METRICS = [
    {"name": "plan_s", "unit": "s", "better": "lower", "source": "program_span",
     "layer": "expression and plan", "moves": "compute_s"},
]
SPANS = {"cubed_tpu.runtime.executors.jax:JaxExecutor.execute_dag": {}}


def read(traced):
    rec = traced.recorder

    def plan(compute):
        root, entered = rec.of("compute", compute), rec.of("JaxExecutor.execute_dag", compute)
        return entered[0].start - root[0].start if root and entered else None

    return traced.median_per_compute(plan)
