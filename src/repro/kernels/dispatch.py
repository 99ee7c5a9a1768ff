"""Which implementation a kernel family runs: one rule for all of them.

impl:
  "xla"              — the pure-jnp oracle (what XLA makes of the math)
  "pallas"           — the Pallas kernel compiled for the TPU
  "pallas_interpret" — the same kernel in interpret mode (CPU tests)

An explicit ``impl=`` wins; then, inside ``second_order()``, "xla" for
a family whose kernels have a first-order backward only; then a scoped
pin (``use_impl``); otherwise the platform decides at call time:
"pallas" on a TPU, "xla" anywhere else. So a chip run always times the
kernels, and a CPU run never tries to compile one. A caller whose path
a kernel cannot serve (the SSD scan has no backward, and the LM loss
differentiates through it) pins "xla" with that family's ``use_impl``
and says why beside the pin.
"""
from __future__ import annotations

import contextlib

import jax

IMPLS = ("xla", "pallas", "pallas_interpret")

_SECOND_ORDER = [False]


@contextlib.contextmanager
def second_order():
    """Trace inside this block a gradient that is differentiated again
    (second-order MAML and Meta-SGD): a ``pallas_call`` in a backward
    cannot be differentiated, so families whose kernels have a
    first-order backward only run their XLA path here."""
    prev, _SECOND_ORDER[0] = _SECOND_ORDER[0], True
    try:
        yield
    finally:
        _SECOND_ORDER[0] = prev


def platform_impl() -> str:
    """"pallas" on a TPU backend, "xla" on any other."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


class ImplChoice:
    """The impl switch of one kernel family (attention, decode, ...).

    The pin is read while tracing: wrap the first call of a jitted
    function, not later replays of an already-compiled executable."""

    def __init__(self, family: str, *, first_order_only: bool = False):
        self.family = family
        self.first_order_only = first_order_only
        self._pinned: str | None = None

    def resolve(self, impl: str | None = None) -> str:
        if impl is None and self.first_order_only and _SECOND_ORDER[0]:
            impl = "xla"
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
