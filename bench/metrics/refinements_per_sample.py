"""Engine: mean Parareal refinements per served sample
(``SampleResponse.iterations``, the engine's own count)."""


def read(run):
    its = [r.iterations for r in run.responses.values() if r.status == "ok"]
    return sum(its) / len(its) if its else None
