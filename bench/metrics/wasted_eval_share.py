"""Engine: the share of the model evals the device ran that served no
sample (masked free lanes, truncation's lockstep width, speculative
refinements of lanes that had converged), over the window:
1 - effective / physical, from the engine's own counters."""


def read(run):
    rs = run.resolves_in_window()
    phys = sum(r[2] for r in rs)
    if not phys:
        return None
    return 100.0 * (1.0 - sum(r[1] for r in rs) / phys)
