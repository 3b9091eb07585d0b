"""Planted faults of the Cannon driver (``bench/drivers/cannon.py``): patches
that break the timed path of ``repro.matmul.cannon`` underneath, or put the
control in its place."""
from unittest import mock

#: Each fault, and the chip counts of the cells it can break: the exchange
#: between chips exists only on a grid of more than one.
FAULTS = {"unchanged": (1, 4), "altered": (1, 4), "no_exchange": (4,)}


def _altered(entry):
    import jax.numpy as jnp

    def wrapped(*args, **kwargs):
        out = entry(*args, **kwargs)
        return out.at[1, 2].add(1.0 + jnp.abs(out[1]).max())
    return wrapped


def patches(kind: str) -> list:
    """``control`` (the reference's control product in the program's place),
    ``unchanged`` (a step returns its state unchanged: every local product
    zero), ``no_exchange`` (the shifts and skew between chips left out) or
    ``altered`` (one answer altered where it is produced)."""
    import jax.numpy as jnp

    from bench.references import matmul as reference
    from repro.matmul import cannon

    zero = lambda a, b, *_: jnp.zeros((a.shape[0], b.shape[1]), jnp.float32)  # noqa: E731
    return {
        "control": [mock.patch.object(
            cannon, "matmul", lambda a, b, _grid: reference.control_product(a, b))],
        "unchanged": [mock.patch.object(cannon, "local_matmul", zero)],
        "no_exchange": [mock.patch.object(cannon, "shift", lambda x, *a: x),
                        mock.patch.object(cannon, "skew", lambda x, *a, **k: x)],
        "altered": [mock.patch.object(cannon, "matmul", _altered(cannon.matmul))],
    }[kind]
