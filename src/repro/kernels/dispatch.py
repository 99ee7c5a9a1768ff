"""Which implementation a kernel family runs: one rule for all of them.

impl:
  "xla"              — the pure-jnp oracle (what XLA makes of the math)
  "pallas"           — the Pallas kernel compiled for the TPU
  "pallas_interpret" — the same kernel in interpret mode (CPU tests)

An explicit ``impl=`` wins; then a scoped pin (``use_impl``); otherwise
the platform decides at call time: "pallas" on a TPU, "xla" anywhere
else. So a chip run always times the kernels, and a CPU run never tries
to compile one. A caller whose path a kernel cannot serve (e.g. the
kernel has no backward and the caller differentiates through it) pins
"xla" with that family's ``use_impl`` and says why beside the pin.
"""
from __future__ import annotations

import contextlib

import jax

IMPLS = ("xla", "pallas", "pallas_interpret")


def platform_impl() -> str:
    """"pallas" on a TPU backend, "xla" on any other."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


class ImplChoice:
    """The impl switch of one kernel family (attention, decode, ...).

    The pin is read while tracing: wrap the first call of a jitted
    function, not later replays of an already-compiled executable."""

    def __init__(self, family: str):
        self.family = family
        self._pinned: str | None = None

    def resolve(self, impl: str | None = None) -> str:
        impl = impl or self._pinned or platform_impl()
        if impl not in IMPLS:
            raise ValueError(f"{self.family}: unknown impl {impl!r}; "
                             f"expected one of {IMPLS}")
        return impl

    @contextlib.contextmanager
    def use(self, impl: str):
        """Pin ``impl`` for this family inside the block (restored on
        exit, also on an exception)."""
        self.resolve(impl)
        prev, self._pinned = self._pinned, impl
        try:
            yield
        finally:
            self._pinned = prev
