"""Engine: seconds the server spent building its serving engine, summed
over its publishes up to the window's end (the include mask's copy to
the host, the ELL refresh and the engine build), from
``stats()["engine_build"]``; None where the server does not count it."""


def read(run):
    build = run.stats1.get("engine_build")
    return None if build is None else float(build["seconds"])
