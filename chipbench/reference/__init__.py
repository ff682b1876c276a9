"""Plain float32 references, one file per model family, and the one
measure of how far a system's logits are from them."""

from __future__ import annotations


def errors(system, reference) -> dict:
    """{"rms", "max"}: root-mean-square and largest |system - reference|,
    each over the standard deviation of the reference's logits."""
    import jax.numpy as jnp

    ref = jnp.asarray(reference, jnp.float32)
    diff = jnp.asarray(system, jnp.float32) - ref
    std = jnp.std(ref)
    return {"rms": float(jnp.sqrt(jnp.mean(diff * diff)) / std),
            "max": float(jnp.max(jnp.abs(diff)) / std)}


def within(err: dict, tolerance: dict) -> bool:
    return err["rms"] <= tolerance["rms"] and err["max"] <= tolerance["max"]
