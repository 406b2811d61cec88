"""One counter over the product of others, in percent.
params: ``numerator``, ``denominators`` (a list)."""


def read(params, obs):
    c = obs.get("counters", {})
    if params["numerator"] not in c:
        return None
    den = 1.0
    for name in params["denominators"]:
        if not c.get(name):
            return None
        den *= c[name]
    return 100.0 * c[params["numerator"]] / den
