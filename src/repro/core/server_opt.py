"""Server-side optimizers over the flat-buffer merge substrate.

The FedAvg-family merge (``flatbuf.FlatServerState``) ends every round
with the packed aggregate ``merged``.  Plain FedAvg *installs* it; a
server optimizer instead treats the implied movement

    d = merged - prev        (prev = the packed server model pre-merge)

as a pseudo-gradient (Reddi et al., "Adaptive Federated Optimization")
and takes a real optimizer step from ``prev`` — one fused elementwise
pass over the same packed buffers, right after the merge contraction and
before the unpack (``kernels.fedavg_agg.server_opt_step_flat``, XLA
oracle in ``kernels.ref``).  State lives as packed ``(N,)`` vectors over
the same :class:`~repro.core.flatbuf.ParamBundle`, so it shards along N
with the substrate (the step is elementwise — no collective) and
checkpoints like any other flat buffer.

Optimizer table
===============

================  =============================================  ==========================
name              update rule (d = merged - prev)                degenerate == plain FedAvg
================  =============================================  ==========================
``fedavgm``       m' = momentum*m + d; new = prev + lr*m'        momentum=0, lr=1
``fedadam``       m' = b1*m + (1-b1)*d; v' = b2*v + (1-b2)*d^2;  beta1=beta2=0, tau=inf
                  new = prev + lr * m' / (sqrt(v') + tau)        (the FedOpt tau->inf limit)
``feddyn``        h' = h + d; new = merged + gamma*h'            gamma=0
================  =============================================  ==========================

Degenerate parameters short-circuit at the Python level and return the
merge result *verbatim* — ``prev + 1.0*(merged - prev)`` is NOT bit-equal
to ``merged`` in f32, so the identity must be structural, not numeric
(pinned by the golden aliases in tests/golden/generate.py).

``feddyn`` is the server half of FedDyn's drift correction: ``h``
accumulates the average client drift and the install overshoots the
aggregate by ``gamma*h``, counteracting the client-drift bias that
non-IID splits induce (the full FedDyn adds a client-side dynamic
regularizer, which in this harness is the worker-side FedProx term —
``models.mlp.mlp_prox_train`` / ``make_setup(fedprox_mu=)``).

Reference path: ``kernels.ref.reference_server_opt`` runs the same
recursions in plain jnp; mapped per leaf it is the parity oracle for the
fused pass (tests/test_server_opt.py, mesh in {1, 2, 4}).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import fedavg_agg, pallas_flags
from repro.parallel import sharding as psharding
from repro.runtime import trace


def _jit_step(mesh, use_pallas: bool, interpret: bool, adam: bool):
    """One jitted fused step per (mesh, flags, form) — cached below so
    repeated rounds hit the jit cache like the merge itself."""
    def step(prev, merged, m, v, scalars):
        if mesh is not None:
            if use_pallas:
                return fedavg_agg.server_opt_step_flat_sharded(
                    prev, merged, m, v, scalars, adam=adam, mesh=mesh,
                    axis=psharding.AGG_AXIS, interpret=interpret)
            vs = psharding.agg_vec_sharding(mesh)
            prev = jax.lax.with_sharding_constraint(prev, vs)
            merged = jax.lax.with_sharding_constraint(merged, vs)
        if use_pallas:
            return fedavg_agg.server_opt_step_flat(
                prev, merged, m, v, scalars, adam=adam, interpret=interpret)
        # XLA path: same math as the kernel, one fused elementwise pass
        sc = scalars.astype(jnp.float32)
        d = merged - prev
        if adam:
            mo = sc[0] * m + (1.0 - sc[0]) * d
            vo = sc[1] * v + (1.0 - sc[1]) * d * d
            return prev + sc[2] * mo / (jnp.sqrt(vo) + sc[3]), mo, vo
        mo = sc[0] * m + sc[1] * d
        return prev + sc[2] * d + sc[3] * mo, mo, None

    return jax.jit(step)


_STEP_JITS: dict = {}


def _step_fn(mesh, use_pallas: Optional[bool], adam: bool):
    use_pallas, interpret = pallas_flags(use_pallas, None)
    key = (mesh, use_pallas, interpret, adam)
    fn = _STEP_JITS.get(key)
    if fn is None:
        fn = _STEP_JITS[key] = _jit_step(mesh, use_pallas, interpret, adam)
    return fn


class ServerOpt:
    """Base: packed-vector optimizer state bound lazily to the merge's
    :class:`~repro.core.flatbuf.ParamBundle` at the first step.

    ``prev`` (the pre-merge packed server) is tracked by tree identity:
    the post-step vector becomes next round's ``prev`` unless the server
    model was replaced externally (checkpoint restore, root failover) —
    then the identity check fails and the anchor re-packs from the tree.
    """

    name = "base"
    adam = False

    def __init__(self):
        self._m = None              # first-moment / drift vector (N,)
        self._v = None              # adam second moment (N,)
        self._prev_vec = None       # packed server model pre-merge
        self._prev_tree = None      # identity key for _prev_vec

    # --- subclass hooks ---
    def _scalars(self) -> np.ndarray:
        raise NotImplementedError

    def _degenerate(self) -> bool:
        """True when the parameters collapse the step to the identity —
        the implementation returns the merge result verbatim (bit-exact
        FedAvg) instead of computing ``prev + 1.0*d``."""
        raise NotImplementedError

    # --- fused flat path (called from FlatServerState merge tails) ---
    def step_vec(self, flat, server_tree, merged):
        """Transform the packed merge result; ``server_tree`` is the
        pre-merge server pytree (the anchor when ``prev`` must re-pack)."""
        if self._degenerate():
            return merged
        if (self._prev_tree is not server_tree or self._prev_vec is None
                or self._prev_vec.is_deleted()):
            # re-pack (bitwise-same for f32): first step, or external
            # model replacement (restore / failover)
            self._prev_vec = flat.bundle.pack(server_tree)
        prev = self._prev_vec
        if self._m is None:
            self._m = jnp.zeros_like(prev)
        if self.adam and self._v is None:
            self._v = jnp.zeros_like(prev)
        new, self._m, v = _step_fn(flat.mesh, flat.use_pallas, self.adam)(
            prev, merged, self._m, self._v,
            trace.to_device(self._scalars(), jnp.float32))
        if self.adam:
            self._v = v
        return new

    def note_result(self, merged_vec, out_tree) -> None:
        """Called by the merge tail after unpack: the post-step vector is
        next round's ``prev`` (keyed on the tree the server will hand
        back)."""
        self._prev_vec = merged_vec
        self._prev_tree = out_tree

    # --- lifecycle ---
    def rebase(self) -> None:
        """The server model was replaced under us (root failover promoted
        a leaf's model to global): drop the packed anchor so the next
        step re-packs from the new tree.  Momentum/second-moment vectors
        survive — they are the ROLE's state, like the ack registry."""
        self._prev_vec = None
        self._prev_tree = None

    def capture(self) -> dict:
        """Checkpoint image: the optimizer vectors only.  The ``prev``
        anchor is re-derived on restore (bitwise-same repack of the
        restored server model, mirroring ``_restore_flat``)."""
        return {"name": self.name, "kw": self._kwargs(),
                "m": self._m, "v": self._v}

    def restore(self, img: dict) -> None:
        self._m = img["m"]
        self._v = img["v"]
        self.rebase()

    def _kwargs(self) -> dict:
        raise NotImplementedError


class FedAvgM(ServerOpt):
    """Server momentum: ``m' = momentum*m + d; new = prev + lr*m'``."""

    name = "fedavgm"

    def __init__(self, momentum: float = 0.9, lr: float = 1.0):
        super().__init__()
        self.momentum = float(momentum)
        self.lr = float(lr)

    def _scalars(self):
        return np.asarray([self.momentum, 1.0, 0.0, self.lr], np.float32)

    def _degenerate(self):
        # momentum=0, lr=1: m' = d and new = prev + d == merged — return
        # it verbatim (the float formula would flip LSBs).  m' need not
        # be materialised: with momentum=0 the next step's m' = d' again
        # regardless of history, so the skipped state is unobservable.
        return self.momentum == 0.0 and self.lr == 1.0

    def _kwargs(self):
        return {"momentum": self.momentum, "lr": self.lr}


class FedAdam(ServerOpt):
    """Per-coordinate adaptive server step (FedOpt's FedAdam, no bias
    correction): ``new = prev + lr * m' / (sqrt(v') + tau)``.  ``tau`` is
    the adaptivity knob; as tau -> inf with lr = tau the step approaches
    the plain FedAvg install (the implementation short-circuits at
    beta1=beta2=0, tau=inf — bit-exact)."""

    name = "fedadam"
    adam = True

    def __init__(self, beta1: float = 0.9, beta2: float = 0.99,
                 lr: float = 0.1, tau: float = 1e-3):
        super().__init__()
        self.beta1 = float(beta1)
        self.beta2 = float(beta2)
        self.lr = float(lr)
        self.tau = float(tau)

    def _scalars(self):
        return np.asarray([self.beta1, self.beta2, self.lr, self.tau,
                           0.0, 0.0], np.float32)

    def _degenerate(self):
        return (self.beta1 == 0.0 and self.beta2 == 0.0
                and math.isinf(self.tau))

    def _kwargs(self):
        return {"beta1": self.beta1, "beta2": self.beta2, "lr": self.lr,
                "tau": self.tau}


class FedDyn(ServerOpt):
    """FedDyn-style server drift correction: ``h`` accumulates the average
    client drift and the install overshoots the aggregate by ``gamma*h``
    (``new = merged + gamma*h'`` — i.e. cd=1, lr=gamma in the momentum
    form with am=bm=1)."""

    name = "feddyn"

    def __init__(self, gamma: float = 0.1):
        super().__init__()
        self.gamma = float(gamma)

    def _scalars(self):
        return np.asarray([1.0, 1.0, 1.0, self.gamma], np.float32)

    def _degenerate(self):
        return self.gamma == 0.0

    def _kwargs(self):
        return {"gamma": self.gamma}


SERVER_OPTS = {
    "fedavgm": FedAvgM,
    "fedadam": FedAdam,
    "feddyn": FedDyn,
}


def make_server_opt(spec, **kw) -> Optional[ServerOpt]:
    """Resolve ``server_opt=`` the way the transport resolves codecs:
    None passes through (plain FedAvg, byte-untouched code path), a
    string looks up :data:`SERVER_OPTS`, an instance is used as-is."""
    if spec is None:
        return None
    if isinstance(spec, ServerOpt):
        if kw:
            raise ValueError("server_opt_kw needs a string server_opt")
        return spec
    cls = SERVER_OPTS.get(spec)
    if cls is None:
        raise ValueError(f"unknown server_opt {spec!r}; "
                         f"have {sorted(SERVER_OPTS)}")
    return cls(**kw)
