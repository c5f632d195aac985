# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.

from repro.core.telemetry import COUNTERS

ROUTE_PREFIX = "kernel.route."


def on_tpu() -> bool:
    """Shared platform probe for the kernel adapters."""
    import jax
    return jax.default_backend() == "tpu"


def record_route(kernel: str, route: str) -> None:
    """Count one launch of `kernel` through `route` (``pallas``,
    ``pallas-interpret`` or ``xla-jit``) under ``kernel.route.*``, so a
    run can show which lowering each decode took."""
    COUNTERS.inc(f"{ROUTE_PREFIX}{kernel}.{route}")


def pallas_interpret(kernel: str, interpret: bool | None) -> bool:
    """The ``interpret`` flag for one Pallas launch of `kernel`, with its
    route recorded. ``None`` picks by platform: compiled on TPU, the
    interpreter elsewhere. On TPU only an explicit ``True`` interprets;
    a kernel that fails to compile raises, it never falls back."""
    if interpret is None:
        interpret = not on_tpu()
    record_route(kernel, "pallas-interpret" if interpret else "pallas")
    return interpret


def route_counts(snapshot: dict) -> dict:
    """{kernel: {route: launches}} from a counter snapshot."""
    out: dict = {}
    for name, n in snapshot.items():
        if name.startswith(ROUTE_PREFIX):
            kernel, route = name[len(ROUTE_PREFIX):].rsplit(".", 1)
            out.setdefault(kernel, {})[route] = int(n)
    return out
