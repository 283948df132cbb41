"""Faults planted under the timed path, to show that the comparison which
decides ``correct`` catches them. Each takes a setter with the signature of
``setattr`` (a test passes ``monkeypatch.setattr``) and replaces one
function of the program for the rest of the process:

* ``altered_token``: the engine's sampler returns the next id for greedy
  lanes, a token altered where it is produced;
* ``flat_density``: the spawn's density term reads the same for every key,
  so the landmark choice follows coverage alone.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --fault flat_density

reads a fault on the chip at the cell's own size. The benchmark's own runs
never plant one.
"""
from __future__ import annotations


def altered_token(set_attr=setattr):
    import jax.numpy as jnp

    import repro.core.engine as engine

    sample = engine.sample_lanes

    def off_by_one(key, logits, lanes, **kw):
        out = sample(key, logits, lanes, **kw)
        return jnp.where(lanes.temperature <= 0, (out + 1) % logits.shape[-1], out)

    set_attr(engine, "sample_lanes", off_by_one)


def flat_density(set_attr=setattr):
    import jax.numpy as jnp

    import repro.core.synapse as synapse

    set_attr(synapse, "kernel_density", lambda q, keys, valid: valid.astype(jnp.float32))


FAULTS = {"altered_token": altered_token, "flat_density": flat_density}
