# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.

def use_pallas_default() -> bool:
    """Shared Pallas-dispatch policy: the platform decides — Pallas only
    on TPU (interpret mode would serialise per block on CPU)."""
    import jax
    return jax.default_backend() == "tpu"


def pallas_flags(use_pallas, interpret):
    """Resolve (use_pallas, interpret) defaults against the backend."""
    import jax
    if use_pallas is None:
        use_pallas = use_pallas_default()
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return bool(use_pallas), bool(interpret)
