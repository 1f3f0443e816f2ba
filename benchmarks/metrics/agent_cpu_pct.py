"""Layer: agent.  CPU seconds of the live agents (their forked commands
included) inside the window over agents x window: 100 = every agent at
a full core."""


def read(run):
    names = run["agent_names"]
    if not names:
        return None
    cpu = sum(run["cpu"].get(n, 0.0) for n in names)
    return cpu / (len(names) * run["window_seconds"]) * 100.0
