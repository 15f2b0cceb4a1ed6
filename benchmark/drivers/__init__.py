"""Operation drivers, one per kind of traffic; a mix file names one.

A driver module has setup(run), which leaves the started cluster in
run.state["cluster"] for the harness to stop, window(run) -> core.Window,
check(run, window) -> [core.Compared] and
end_to_end(window) -> {metric: value}."""
