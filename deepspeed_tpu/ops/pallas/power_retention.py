"""Power retention (degree-2 gated linear attention) over a recurrent
state: the chunked prefill kernel, the state-update decode kernel, and
the same two computations in plain ``jax.numpy``.

The layer (Buckman, Gelada, Zhang, Bach, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239), for a query head ``n`` of
key/value head ``m``, ``d`` the head size, ``g_t`` in (0, 1] a gate a
key/value head a token and ``G_t = sum_{s<=t} log g_s``::

    a_tj   = (q_t . k_j / sqrt(d))^2 exp(G_t - G_j)            j <= t
    y_t    = sum_j a_tj v_j / (sum_j a_tj + eps)

``(q . k)^2`` is an inner product of degree-2 features, so the same
function is a recurrence over a state of fixed size::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T     z_t = g_t z_{t-1} + phi(k_t)
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

**The stored layout of phi.** ``phi(u)`` is laid out as the ``R = d/2 +
1`` circulant diagonals of ``u u^T``, ``d`` values each::

    phi(u)[r, i] = c_r u_i u_{(i - r) mod d} / sqrt(d)
    c_0 = c_{d/2} = 1,   c_r = sqrt(2) otherwise

Diagonal 0 holds the ``d`` squares; diagonals ``0 < r < d/2`` hold every
unordered pair at circular distance ``r`` once; diagonal ``d/2`` holds
each of its ``d/2`` pairs twice with weight 1 where a packed triangle
holds it once with weight ``sqrt(2)``. So ``phi(q) . phi(k) = (q . k /
sqrt(d))^2`` exactly, in ``R d`` = 8320 values at ``d`` = 128 against the
triangle's 8256 (0.8 % more) and the unpacked ``vec(u u^T)``'s 16384. A
diagonal is ``u`` times a lane rotation of ``u``: the layout needs no
gather, and a diagonal's ``[d_v, d]`` tile of the state is exactly one
``(128, 128)`` tile of the chip.

**The state** of one sequence, one layer, one key/value head is ``S [R,
d_v, d]`` (``S[r, c, i] = sum_j decay v_j[c] phi(k_j)[r, i]``: the value
index down the sublanes, the pair index along the lanes) and ``z [R,
d]``, float32 (the pool's type is the configuration's ``state_dtype``; the
kernels compute in float32 whatever it is). A pool is ``S [slots, KH, R, d_v, d]``, ``z [slots, KH, Rz,
d]`` (``Rz``: ``R`` rounded up to 8, :func:`z_rows`).

**The kernels.** ``power_retention_prefill`` runs one right-padded prompt
in chunks of ``C`` tokens: inside a chunk the attention form (scores
squared and decayed, no softmax, no running maximum), across chunks
``phi(Q_c) S_{c-1}`` a diagonal at a time on the MXU, and ``S_c = decay
S_{c-1} + (decay V_c)^T phi(K_c)``; ``phi`` lives in VMEM a diagonal at a
time and is never written to HBM. The slot's state is the kernel's
resident output block; the rest of the (aliased) pool is not touched.
``power_retention_decode`` reads each live slot's state once, writes ``g
S + v phi(k)^T`` back IN PLACE (``input_output_aliases``) and takes the
query heads' outputs from the same pass, all in float32 on the VPU: the
state crosses HBM once each way a step. Idle slots cost nothing: live
slot ids are compacted to the front of the grid and the idle tail
re-names the last block, so no DMA is issued for it.

The function pairs ``*_reference`` are the models' path off the TPU and
the kernels' oracles.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
DECODE_NAME = "power_retention_decode"
PREFILL_NAME = "power_retention_prefill"
_HIGHEST = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 96 * 1024 * 1024


def pair_rows(d: int) -> int:
    """Diagonals ``R`` of the stored ``phi`` of a ``d``-wide head."""
    if d % 2:
        raise ValueError(f"power retention needs an even head size, not {d}")
    return d // 2 + 1


def z_rows(d: int) -> int:
    """Rows of a stored ``z``: ``R`` rounded up to the 8 sublanes of a
    tile (the rows past ``R`` are never read). With 65 rows the compiler
    stores ``[slots, KH, 65, d]`` with the 8 heads innermost to save the
    padding, and converts the pool around every kernel call."""
    return -(-pair_rows(d) // 8) * 8


def pair_coef(d: int) -> jax.Array:
    """``c_r`` ``[R]``: 1 on the squares and on the doubled diagonal
    ``d/2``, ``sqrt(2)`` between."""
    R = pair_rows(d)
    r = jnp.arange(R)
    return jnp.where((r == 0) | (r == R - 1), 1.0, math.sqrt(2.0)).astype(F32)


def phi(u: jax.Array) -> jax.Array:
    """``u [..., d]`` (already divided by ``d ** 0.25``) -> the stored
    features ``[..., R, d]`` float32."""
    u = u.astype(F32)
    d = u.shape[-1]
    rolled = jnp.stack([jnp.roll(u, r, axis=-1)
                        for r in range(pair_rows(d))], axis=-2)
    return pair_coef(d)[:, None] * u[..., None, :] * rolled


def _mm(dtype):
    """How a matmul of this operand type is asked for: float32 operands
    at full precision, narrower ones as they are (float32 accumulation
    either way)."""
    return dict(preferred_element_type=F32,
                precision=_HIGHEST if dtype == F32 else None)


# ------------------------------------------------------------ plain jnp

def retention_chunked_reference(q, k, v, log_g, length, *, chunk: int,
                                eps: float):
    """The chunked form of one right-padded sequence in ``jax.numpy``.

    ``q [T, KH, G, d]``, ``k [T, KH, d]``, ``v [T, KH, dv]`` (head norm
    and rotary applied, not yet scaled), ``log_g [T, KH]`` float32,
    ``length`` the live tokens (a traced scalar). Tokens at or past
    ``length`` neither decay nor feed the state. Returns ``y [T, KH, G,
    dv]`` in ``q``'s type and the final ``S [KH, R, dv, d]``, ``z [KH, R,
    d]`` float32. Matmul operands are ``q``'s type (float32 at full
    precision), accumulation float32."""
    T, KH, G, d = q.shape
    dv, dt = v.shape[-1], q.dtype
    R = pair_rows(d)
    C = min(chunk, T)
    Tp = -(-T // C) * C
    pad = [(0, Tp - T)]
    scale = d ** -0.25
    valid = (jnp.arange(Tp) < length)[:, None]
    q = jnp.pad(q.astype(F32) * scale, pad + [(0, 0)] * 3)
    k = jnp.where(valid[..., None],
                  jnp.pad(k.astype(F32) * scale, pad + [(0, 0)] * 2), 0.0)
    v = jnp.where(valid[..., None],
                  jnp.pad(v.astype(F32), pad + [(0, 0)] * 2), 0.0)
    lg = jnp.where(valid, jnp.pad(log_g.astype(F32), pad + [(0, 0)]), 0.0)
    mm = _mm(dt)
    causal = jnp.tril(jnp.ones((C, C), bool))

    def step(carry, xs):
        S, z = carry
        qc, kc, vc, lgc = xs                     # [C, KH, ...]
        Gc = jnp.cumsum(lgc, axis=0)             # [C, KH]
        gtot = Gc[-1]                            # [KH]
        s = jnp.einsum("tmgd,jmd->mgtj", qc.astype(dt), kc.astype(dt), **mm)
        dec = jnp.exp(jnp.minimum(Gc.T[:, :, None] - Gc.T[:, None, :], 0.0))
        a = jnp.where(causal, s * s * dec[:, None], 0.0)     # [KH, G, C, C]
        num = jnp.einsum("mgtj,jmv->tmgv", a.astype(dt), vc.astype(dt), **mm)
        den = jnp.moveaxis(a.sum(-1), -1, 0)                 # [C, KH, G]
        pq = phi(qc)                                         # [C,KH,G,R,d]
        b = jnp.exp(Gc)                                      # [C, KH]
        num = num + b[..., None, None] * jnp.einsum(
            "tmgrd,mrvd->tmgv", pq.astype(dt), S.astype(dt), **mm)
        den = den + b[..., None] * jnp.einsum("tmgrd,mrd->tmg", pq, z,
                                              precision=_HIGHEST)
        y = num / (den + eps)[..., None]
        w = jnp.exp(gtot[None] - Gc)                         # [C, KH]
        pk = phi(kc)                                         # [C, KH, R, d]
        e = jnp.exp(gtot)
        S = e[:, None, None, None] * S + jnp.einsum(
            "jmv,jmrd->mrvd", (vc * w[..., None]).astype(dt), pk.astype(dt),
            **mm)
        z = e[:, None, None] * z + jnp.einsum("jm,jmrd->mrd", w, pk,
                                              precision=_HIGHEST)
        return (S, z), y.astype(dt)

    def chunks(x):
        return x.reshape(Tp // C, C, *x.shape[1:])
    init = (jnp.zeros((KH, R, dv, d), F32), jnp.zeros((KH, R, d), F32))
    (S, z), y = jax.lax.scan(step, init, tuple(map(chunks, (q, k, v, lg))))
    return y.reshape(Tp, KH, G, dv)[:T], S, z


def retention_decode_reference(q, k, v, log_g, active, S, z, *, eps: float):
    """One token a slot through the state, in ``jax.numpy`` float32.

    ``q [slots, KH, G, d]``, ``k [slots, KH, d]``, ``v [slots, KH, dv]``,
    ``log_g [slots, KH]``, ``active [slots]`` bool, ``S [slots, KH, R, dv,
    d]``, ``z [slots, KH, Rz, d]``. Idle slots keep their state and return
    zeros. Returns ``(y [slots, KH, G, dv] in q's type, S, z)``."""
    d = q.shape[-1]
    scale = d ** -0.25
    g = jnp.exp(log_g.astype(F32))
    pk = phi(k.astype(F32) * scale)                       # [s, KH, R, d]
    pq = phi(q.astype(F32) * scale)                       # [s, KH, G, R, d]
    S_new = (g[..., None, None, None] * S.astype(F32)
             + v.astype(F32)[:, :, None, :, None] * pk[:, :, :, None, :])
    R = pk.shape[-2]
    z_new = g[..., None, None] * z[:, :, :R].astype(F32) + pk
    num = jnp.einsum("smgrd,smrvd->smgv", pq, S_new, precision=_HIGHEST)
    den = jnp.einsum("smgrd,smrd->smg", pq, z_new, precision=_HIGHEST)
    live = active.astype(bool)
    y = jnp.where(live[:, None, None, None], num / (den + eps)[..., None],
                  0.0)
    return (y.astype(q.dtype),
            jnp.where(live[:, None, None, None, None],
                      S_new.astype(S.dtype), S),
            z.at[:, :, :R].set(jnp.where(
                live[:, None, None, None], z_new.astype(z.dtype),
                z[:, :, :R])))


# ------------------------------------------------------------- prefill

def _prefill_kernel(slot_ref, len_ref, q_ref, k_ref, v_ref, gcol_ref,
                    grow_ref, gtot_ref, s_any, z_any, y_ref, s_out, z_out,
                    accy, accd, qf, qroll, kroll, zs, *, G, C, R, d, eps,
                    mm_dtype):
    """Grid (key/value head, chunk). ``s_out`` / ``z_out`` are this head's
    state in the slot: resident across the chunks, zeroed at the first,
    written back to the pool after the last. ``z`` is worked on a row at
    a time in the float32 scratch ``zs`` (a row of a narrower stored type
    cannot be addressed alone) and stored whole after every chunk."""
    del slot_ref, s_any, z_any
    m, c = pl.program_id(0), pl.program_id(1)
    mm = _mm(mm_dtype)
    root2 = math.sqrt(2.0)

    @pl.when(c == 0)
    def _fresh():
        s_out[...] = jnp.zeros_like(s_out)
        z_out[...] = jnp.zeros_like(z_out)
        zs[...] = jnp.zeros_like(zs)

    @pl.when(c * C >= len_ref[0])
    def _padding():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(c * C < len_ref[0])
    def _chunk():
        q = q_ref[0, 0].astype(F32)                      # [G C, d]
        k = k_ref[0].astype(F32)                         # [C, d]
        v = v_ref[0].astype(F32)                         # [C, dv]
        gcol = gcol_ref[0]                               # [C, 1]
        grow = grow_ref[0, 0]                            # [1, C]
        gq = jnp.concatenate([gcol] * G, axis=0)         # [G C, 1]
        gtot = gtot_ref[m, c]              # the chunk's whole log decay
        # inside the chunk: the attention form
        s = jax.lax.dot_general(q.astype(mm_dtype), k.astype(mm_dtype),
                                (((1,), (1,)), ((), ())), **mm)
        t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % C
        j = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        a = jnp.where(j <= t,
                      s * s * jnp.exp(jnp.minimum(gq - grow, 0.0)), 0.0)
        num = jnp.dot(a.astype(mm_dtype), v.astype(mm_dtype), **mm)
        den = jnp.sum(a, axis=1, keepdims=True)
        # across chunks: a diagonal of phi at a time
        wcol = jnp.exp(gtot - gcol)                      # [C, 1]
        vdT = (v * wcol).T.astype(mm_dtype)              # [dv, C]
        etot = jnp.exp(gtot)
        qf[...] = q
        qroll[...] = q
        kroll[...] = k

        def diagonal(r, read: bool):
            coef = jnp.where((r == 0) | (r == R - 1), 1.0, root2)
            kr = coef * k_ref[0].astype(F32) * kroll[...]        # [C, d]
            S_r = s_out[0, 0, r].astype(F32)                     # [dv, d]
            z_r = zs[pl.ds(r, 1), :]                             # [1, d]
            if read:
                qr = coef * qf[...] * qroll[...]                 # [G C, d]
                accy[...] += jax.lax.dot_general(
                    qr.astype(mm_dtype), S_r.astype(mm_dtype),
                    (((1,), (1,)), ((), ())), **mm)
                accd[...] += qr * z_r
                qroll[...] = pltpu.roll(qroll[...], 1, 1)
            s_out[0, 0, r] = (etot * S_r + jnp.dot(
                vdT, kr.astype(mm_dtype), **mm)).astype(s_out.dtype)
            zs[pl.ds(r, 1), :] = etot * z_r + jnp.sum(
                kr * wcol, axis=0, keepdims=True)
            kroll[...] = pltpu.roll(kroll[...], 1, 1)

        def store_z():                # as the pool's type keeps it
            z_out[0, 0] = zs[...].astype(z_out.dtype)
            zs[...] = z_out[0, 0].astype(F32)

        @pl.when(c == 0)
        def _first():                 # the state is empty: nothing to read
            jax.lax.fori_loop(
                0, R, lambda r, _: diagonal(r, False), None)
            store_z()
            y_ref[0, 0] = (num / (den + eps)).astype(y_ref.dtype)

        @pl.when(c > 0)
        def _later():
            accy[...] = jnp.zeros_like(accy)
            accd[...] = jnp.zeros_like(accd)
            jax.lax.fori_loop(
                0, R, lambda r, _: diagonal(r, True), None)
            store_z()
            bq = jnp.exp(gq)
            y_ref[0, 0] = ((num + bq * accy[...]) / (
                den + bq * jnp.sum(accd[...], axis=1, keepdims=True)
                + eps)).astype(y_ref.dtype)


def power_retention_prefill(q, k, v, log_g, length, S, z, slot, *,
                            chunk: int, eps: float,
                            interpret: bool | None = None):
    """The chunked form of one right-padded prompt, its final state
    written into ``slot`` of the pool in place.

    ``q [T, KH, G, d]``, ``k [T, KH, d]``, ``v [T, KH, dv]`` (head norm and
    rotary applied), ``log_g [T, KH]`` float32, ``length`` and ``slot``
    traced int32 scalars, ``S [slots, KH, R, dv, d]`` and ``z [slots, KH,
    Rz, d]`` float32 (donate them). ``T`` is a multiple of ``min(chunk,
    T)``. Returns ``(y [T, KH, G, dv] in q's type, S, z)``; only
    ``slot``'s part of the pool is written."""
    T, KH, G, d = q.shape
    dv, dt = v.shape[-1], q.dtype
    R = pair_rows(d)
    C = min(chunk, T)
    if T % C:
        raise ValueError(f"a prompt bucket of {T} tokens is not a whole "
                         f"number of chunks of {C}")
    Rz = z_rows(d)
    if S.shape[1:] != (KH, R, dv, d) or z.shape[1:] != (KH, Rz, d):
        raise ValueError(f"state pool {S.shape} / {z.shape} is not "
                         f"[slots, {KH}, {R}, {dv}, {d}] / [slots, {KH}, "
                         f"{Rz}, {d}]")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    NC = T // C
    scale = d ** -0.25
    length = jnp.reshape(length, ()).astype(jnp.int32)
    valid = (jnp.arange(T) < length)[:, None]
    qs = (q.astype(F32) * scale).astype(dt)
    qs = qs.reshape(NC, C, KH, G, d).transpose(2, 0, 3, 1, 4).reshape(
        KH, NC, G * C, d)
    ks = jnp.where(valid[..., None], k.astype(F32) * scale, 0.0).astype(dt)
    vs = jnp.where(valid[..., None], v, jnp.zeros((), v.dtype))
    lg = jnp.where(valid, log_g.astype(F32), 0.0)
    Gc = jnp.cumsum(lg.reshape(NC, C, KH), axis=1)        # within a chunk
    gcol = Gc.transpose(2, 0, 1).reshape(KH, T, 1)
    grow = Gc.transpose(2, 0, 1).reshape(KH, NC, 1, C)
    gtot = Gc[:, -1].T                                    # [KH, NC]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(KH, NC),
        in_specs=[
            pl.BlockSpec((1, 1, G * C, d), lambda m, c, *_: (m, c, 0, 0)),
            pl.BlockSpec((1, C, d), lambda m, c, *_: (m, c, 0)),
            pl.BlockSpec((1, C, dv), lambda m, c, *_: (m, c, 0)),
            pl.BlockSpec((1, C, 1), lambda m, c, *_: (m, c, 0)),
            pl.BlockSpec((1, 1, 1, C), lambda m, c, *_: (m, c, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[
            pl.BlockSpec((1, 1, G * C, dv), lambda m, c, *_: (m, c, 0, 0)),
            pl.BlockSpec((1, 1, R, dv, d),
                         lambda m, c, slot, n: (slot[0], m, 0, 0, 0)),
            pl.BlockSpec((1, 1, Rz, d),
                         lambda m, c, slot, n: (slot[0], m, 0, 0))],
        scratch_shapes=[pltpu.VMEM((G * C, dv), F32),     # accy
                        pltpu.VMEM((G * C, d), F32),      # accd
                        pltpu.VMEM((G * C, d), F32),      # qf
                        pltpu.VMEM((G * C, d), F32),      # qroll
                        pltpu.VMEM((C, d), F32),          # kroll
                        pltpu.VMEM((Rz, d), F32)])        # zs
    y, S, z = pl.pallas_call(
        functools.partial(_prefill_kernel, G=G, C=C, R=R, d=d,
                          eps=float(eps), mm_dtype=dt),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((KH, NC, G * C, dv), dt),
                   jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)],
        input_output_aliases={8: 1, 9: 2},   # after slot, length, 6 inputs
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=PREFILL_NAME,
    )(jnp.reshape(slot, (1,)).astype(jnp.int32), jnp.reshape(length, (1,)),
      qs, ks.transpose(1, 0, 2), vs.transpose(1, 0, 2), gcol, grow, gtot, S,
      z)
    y = y.reshape(KH, NC, G, C, dv).transpose(1, 3, 0, 2, 4)
    return y.reshape(T, KH, G, dv), S, z


# -------------------------------------------------------------- decode

def _decode_kernel(ids_ref, n_ref, x_ref, s_in, z_in, y_ref, s_out, z_out,
                   *, G, R, d, rows, eps):
    """Grid (live position, key/value head). ``x`` holds the head's ``G``
    scaled queries, then the scaled key, the value and the gate (a row
    each, the gate repeated along the row). float32 throughout."""
    del ids_ref
    p = pl.program_id(0)
    root2 = math.sqrt(2.0)

    @pl.when(p < n_ref[0])
    def _live():
        x = x_ref[0, 0]                                  # [G + 3, d]
        q, k = x[:G], x[G:G + 1]
        v, g = x[G + 1:G + 2], x[G + 2:G + 3]
        dv = v.shape[-1]
        # the value down the sublanes: vcol[c, i] = v[c] for every lane i
        vcol = jnp.broadcast_to(v, (dv, dv)).T
        feats = []                       # (phi(k)[r], phi(q)[r]) rows
        den = jnp.zeros((G, d), F32)
        kroll, qroll = k, q
        for r in range(R):
            coef = 1.0 if r in (0, R - 1) else root2
            kr, qr = coef * k * kroll, coef * q * qroll
            z_new = g * z_in[0, 0, r:r + 1, :].astype(F32) + kr
            z_out[0, 0, r:r + 1, :] = z_new.astype(z_out.dtype)
            den = den + qr * z_new
            feats.append((kr, qr))
            if r + 1 < R:
                kroll = pltpu.roll(kroll, 1, 1)
                qroll = pltpu.roll(qroll, 1, 1)
        den = jnp.sum(den, axis=1, keepdims=True) + eps      # [G, 1]
        # the state, ``rows`` values at a time (the G accumulators of a
        # whole [dv, d] tile would not stay in registers)
        for lo in range(0, dv, rows):
            acc = [jnp.zeros((rows, d), F32) for _ in range(G)]
            vc = vcol[lo:lo + rows]
            for r, (kr, qr) in enumerate(feats):
                S_new = (g * s_in[0, 0, r, lo:lo + rows, :].astype(F32)
                         + vc * kr)
                s_out[0, 0, r, lo:lo + rows, :] = S_new.astype(s_out.dtype)
                for n in range(G):
                    acc[n] = acc[n] + S_new * qr[n:n + 1]
            for n in range(G):
                y_ref[0, 0, lo:lo + rows, n:n + 1] = jnp.sum(
                    acc[n], axis=1, keepdims=True) / den[n:n + 1]


def power_retention_decode(q, k, v, log_g, active, S, z, *, eps: float,
                           interpret: bool | None = None):
    """One token a slot: every live slot's state updated in place and
    read for its query heads in the same pass.

    ``q [slots, KH, G, d]``, ``k [slots, KH, d]``, ``v [slots, KH, d]``
    (head norm and rotary applied), ``log_g [slots, KH]``, ``active
    [slots]`` bool, ``S [slots, KH, R, d, d]``, ``z [slots, KH, Rz, d]``
    float32 (donate them). Returns ``(y [slots, KH, G, d] in q's type, S,
    z)``; an idle slot's state is neither read nor written and its ``y``
    is zero."""
    slots, KH, G, d = q.shape
    R = pair_rows(d)
    if v.shape[-1] != d:
        raise ValueError("the decode kernel keeps keys and values in one "
                         f"array: value size {v.shape[-1]} != key size {d}")
    Rz = z_rows(d)
    if S.shape != (slots, KH, R, d, d) or z.shape != (slots, KH, Rz, d):
        raise ValueError(f"state pool {S.shape} / {z.shape} is not "
                         f"[{slots}, {KH}, {R}, {d}, {d}] / [.., {Rz}, {d}]")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = d ** -0.25
    live = active.astype(bool)
    x = jnp.concatenate([
        q.astype(F32) * scale, (k.astype(F32) * scale)[:, :, None],
        v.astype(F32)[:, :, None],
        jnp.broadcast_to(jnp.exp(log_g.astype(F32))[:, :, None, None],
                         (slots, KH, 1, d))], axis=2)     # [s, KH, G + 3, d]
    # live slots first; the idle tail re-names the last live block (no
    # DMA, and ``pl.when`` skips its compute)
    n_live = jnp.sum(live, dtype=jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    ids = jnp.where(jnp.arange(slots) < n_live, order,
                    order[jnp.maximum(n_live - 1, 0)])

    def at(*tail):
        def index(p, m, ids, n):
            return (ids[p], jnp.where(p < n[0], m, KH - 1), *tail)
        return index

    rows = min(d, 64)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, KH),
        in_specs=[pl.BlockSpec((1, 1, G + 3, d), at(0, 0)),
                  pl.BlockSpec((1, 1, R, d, d), at(0, 0, 0)),
                  pl.BlockSpec((1, 1, Rz, d), at(0, 0))],
        out_specs=[pl.BlockSpec((1, 1, d, G), at(0, 0)),
                   pl.BlockSpec((1, 1, R, d, d), at(0, 0, 0)),
                   pl.BlockSpec((1, 1, Rz, d), at(0, 0))])
    yT, S, z = pl.pallas_call(
        functools.partial(_decode_kernel, G=G, R=R, d=d, rows=rows,
                          eps=float(eps)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((slots, KH, d, G), F32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)],
        input_output_aliases={3: 1, 4: 2},     # after ids, n_live, x
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=DECODE_NAME,
    )(ids, jnp.reshape(n_live, (1,)), x, S, z)
    y = jnp.where(live[:, None, None, None], jnp.swapaxes(yT, 2, 3), 0.0)
    return y.astype(q.dtype), S, z
