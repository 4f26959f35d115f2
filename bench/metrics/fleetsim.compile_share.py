"""Share of the traced experiment's wall time that the fleet engine
spent lowering and compiling the macro scan of the experiment's seed:
the program's `phase/jax_compile_wall_s` gauge."""


def read(obs):
    value = obs.get("counters", {}).get("jax_compile_wall_s")
    if value is None:
        return None
    return 100.0 * value / obs["trace"].window_s
