"""Programs traced after warm-up: a stand-in for the compiles and retraces
inside the timed window, read after it.

Warm-up calls every entry point the window uses: ``run_pipeline`` and
``pipeline_tick_jit`` once under each delay profile, the initial state and
the commit gate once. A later call whose arguments differ from all those
(a shape, a dtype, a weak type or a static argument) traces and compiles
the program again, and leaves one more entry in the entry point's jit
cache. This counts the entries beyond warm-up's after the window, the
traced phase and the counters' replay, so a retrace in any of them counts.
It cannot see a compile that leaves the entry count as it was, nor tell
the window from the phases after it; in a process that has run other
deployments the module-level caches hold their entries too. Reads nothing
where JAX's jit objects keep no countable cache."""


def read(run):
    prog = run.program
    profiles = len(set(prog.cfgs))
    want = ((prog._run, profiles), (prog._tick, profiles),
            (prog._init, 1), (prog._committed, 1))
    try:
        sizes = [(fn._cache_size(), n) for fn, n in want]
    except AttributeError:
        return None
    return sum(max(0, got - n) for got, n in sizes)
