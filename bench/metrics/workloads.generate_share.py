"""Share of the traced experiment's wall time spent in the program's
request generation (`get_scenario(...).generate`, host span
`bench.generate`)."""


def read(obs):
    summary = obs["trace"]
    if "generate" not in summary.spans:
        return None
    return 100.0 * summary.span_s("generate") / summary.window_s
