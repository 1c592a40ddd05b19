"""Host seconds per plan in the program's scenario generator
(``data.scenarios.scenario_batch``), from the benchmark's trace
annotation round it.  Nothing to read where the traffic has no
scenarios."""


def read(record):
    spans = record["scenario_gen_s"]
    if not spans or not record["plans"]:
        return None
    return sum(spans) / record["plans"]
