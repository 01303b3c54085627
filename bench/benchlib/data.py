"""What a run draws from its seed besides the fleet's data, which its
model family makes (``families/<family>.py``)."""
from __future__ import annotations


def base_seed(seed: int) -> int:
    """The run's seed folded into what ``jax.random.PRNGKey`` keeps (31
    bits), with room above for the sweep's consecutive variant seeds."""
    return int(seed) % (2 ** 31 - 4096)


def describe(config: dict, traffic: dict, seed: int) -> dict:
    """What the run draws from its seed besides the data: one entry per
    variant (its init and sampling seeds and its mobility scenario, if
    any), and the fault and redundancy plans with their seeds. The entry
    and the reference are both built from this."""
    b = base_seed(seed)

    def seeded(d):
        return None if d is None else dict(d, seed=b)

    sweep = traffic.get("variants")
    if sweep is None:
        variants = [{"init_seed": b, "sample_seed": b + 1,
                     "mobility": seeded(traffic.get("mobility"))}]
    else:
        # cross product, seed outermost: the order of a nested loop
        variants = [{"init_seed": s, "sample_seed": s + 1,
                     "mobility": seeded(mob)}
                    for s in range(b, b + sweep["seeds"])
                    for mob in sweep["mobility"]]
    return {"variants": variants,
            "faults": seeded(traffic.get("faults")),
            "ingest": seeded(config.get("ingest"))}
