"""A program counter as it stands. params: ``counter``."""


def read(params, obs):
    return obs.get("counters", {}).get(params["counter"])
