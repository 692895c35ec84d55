"""plan_compile_s: host clock around repro.api.operator(...) and the
autotune_report() call that compiles the plan."""


def read(run):
    return run.setup["plan_compile"]
