"""Share of the requests' wall clock the phase clock (serving/obs.py) gave
to prefill, over the requests that finished in the window. Sound while
every engine step ends in a blocking fetch (PERF.md section 3)."""


def read(obs):
    if obs["kind"] != "serve":
        return None
    d = {ph: obs["after"]["phases"][ph] - obs["before"]["phases"][ph]
         for ph in obs["after"]["phases"]}
    total = sum(d.values())
    return 100.0 * d["prefill"] / total if total > 0 else None
