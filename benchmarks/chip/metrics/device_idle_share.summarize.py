"""Share of the traced job in which no operation ran on the device, in
percent: 100 * (1 - busy / window) from the profiler trace
(`chipbench.trace`)."""


def read(obs):
    red = obs.get("trace")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
