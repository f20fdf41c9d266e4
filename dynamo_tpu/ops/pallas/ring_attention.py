"""Latency-hiding Pallas ring attention for sequence-parallel prefill.

The XLA ring (`ops/ring_attention.py`) rotates K/V blocks with
`lax.ppermute` and HOPES the scheduler overlaps each hop with the local
einsums — nothing guarantees it, and the per-hop `s`/`p` intermediates
round-trip HBM.  This kernel makes the overlap structural (blockwise
ring attention, Liu et al.): each shard keeps its Q block resident in
VMEM with online-softmax (m, l, acc) state, and the NEXT hop's K/V
block — absolute positions and, when quantized, the int8 rows' `[T,
Hkv]` f32 scales riding with them exactly as on the XLA path — is
shipped over ICI via double-buffered `make_async_remote_copy` RDMA
issued BEFORE the local block's compute.  The transfer hides under the
flash fold on every hop instead of being scheduled on faith.

Numerics mirror `ring_causal_attention` (same visiting order starting
at the shard's own block, same f32 softmax path, same `NEG` mask fill),
so the XLA ring stays the oracle: `tests/test_ring_kernel.py` pins
kernel == XLA ring == meshless `causal_attention` for bf16 and int8.
The int8 path feeds the int8 rows to the matmuls unscaled and multiplies
the `[1, T_loc]` scale rows into the scores and probabilities — equal to
the XLA ring's dequantize-then-contract except that the dequantized
operand is not first rounded to the compute dtype.

Hardware sync protocol (runs in interpret mode too):

- an initial neighbor barrier (`get_barrier_semaphore`) so no shard
  RDMAs into a peer that hasn't entered the kernel;
- credit-based ack backpressure: the send at step s writes the
  receiver's slot (s+1) % 2, which the receiver last reads at step
  s-1 — so the sender waits for the receiver's ack before the send at
  every step >= 1, and each shard acks its LEFT neighbor (the device
  writing into its buffers) after folding a slot it will never read
  again.

Eligibility is `ring_geometry_ok` (the mosaic_geometry_ok discipline:
one predicate shared by the model's trace-time dispatch and the
engine's kernel-path counter, so they can never disagree on which path a
geometry runs: tests/test_ring_kernel.py::
test_geometry_gate_and_shared_predicate); ineligible shapes fall back
to the XLA ppermute path loudly at the dispatch site.

Interpret mode: CPU tier-1 runs the kernel body end to end — remote
copies, barrier and ack semaphores included — through Pallas's TPU
interpreter (`pltpu.InterpretParams`), which simulates every device of
the repo's five-axis meshes (dp, pp, sp, ep, tp) with the same
row-major LOGICAL device ids the kernel computes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30

# ---------------------------------------------------------------------------
# Eligibility


# What the v5e compiler reported for the kernel's scoped VMEM (q, out,
# both K/V slots and the unrolled per-head flash state all live at once)
# at Hq*D = 2048: 17.88 MB at T_loc 256 and 53.89 MB at T_loc 512, i.e.
# ~18 bytes per (row x q feature) plus ~5 bytes per (head x T_loc^2).
# The default scoped limit is 16 MiB; stay a little under it.
_VMEM_BUDGET = 14 * 1024 * 1024


def ring_vmem_bytes(t_local: int, batch: int, q_heads: int,
                    head_dim: int) -> int:
    """Modelled scoped VMEM of one kernel instance (see `_VMEM_BUDGET`);
    all arguments are PER SHARD."""
    return (batch * t_local * q_heads * head_dim * 18
            + batch * q_heads * t_local * t_local * 5)


def ring_geometry_ok(feat: int, t_local: int, batch: int, q_heads: int,
                     head_dim: int) -> bool:
    """THE eligibility rule for the compiled ring kernel, all arguments
    per shard: the K/V feature width (F/tp under head-sharded tp) must
    fill the 128 lanes, the chunk length too (the rotating positions and
    int8 scales ride with tokens on the lanes), and the whole-chunk
    working set must fit the scoped VMEM limit — at llama-3-1b widths
    that admits T_loc 128 and refuses 256.  Shared by the trace-time
    dispatch in `models/llama._sp_ring_attention` and the engine's
    kernel-path counter — the same discipline as `mosaic_geometry_ok` —
    so the counter says which path the compiled program runs
    (tests/test_compose_matrix.py's sp2 cells hold the two together)."""
    return (feat % 128 == 0 and t_local % 128 == 0 and t_local > 0
            and ring_vmem_bytes(t_local, batch, q_heads, head_dim)
            <= _VMEM_BUDGET)


def ring_kernel_supported(feat: int, t_local: int, batch: int,
                          q_heads: int, head_dim: int,
                          interpret: bool) -> bool:
    """The ONE kernel-vs-XLA-ring selection predicate (engine counter,
    model dispatch, tools).  Compiled mode needs Mosaic-legal geometry;
    interpret mode runs ANY shape (nothing lowers through Mosaic — this
    is how CPU tier-1 exercises the kernel body at tiny geometry)."""
    return interpret or ring_geometry_ok(feat, t_local, batch, q_heads,
                                         head_dim)


# ---------------------------------------------------------------------------
# Kernel


def _flash_fold(q_ref, qpos_col_ref, k_buf, v_buf, pos_buf, ks_buf,
                vs_buf, cur, state, *, B, t_loc, Hq, G, D, soft_cap):
    """Fold the visiting K/V block (buffer slot `cur`) into the
    (m, l, acc) state — the same update `ring_causal_attention` applies
    per ppermute step, on 2D tiles: per (batch row, q head) a
    [T_loc, D] x [D, T_loc] MXU matmul in f32."""
    for b in range(B):
        r0 = b * t_loc
        # mask[t, c]: visiting key c attends query t iff its absolute
        # position is <= the query's (causality carried by the rotating
        # positions, correct for any block interleaving).
        mask = (pos_buf[cur, b:b + 1, :]
                <= qpos_col_ref[r0:r0 + t_loc, :])
        for h in range(Hq):
            hk = h // G
            q_h = q_ref[r0:r0 + t_loc, h * D:(h + 1) * D]
            k_h = k_buf[cur, r0:r0 + t_loc, hk * D:(hk + 1) * D]
            v_h = v_buf[cur, r0:r0 + t_loc, hk * D:(hk + 1) * D]
            k_h = k_h.astype(jnp.float32)
            v_h = v_h.astype(jnp.float32)
            s = jax.lax.dot_general(
                q_h, k_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if ks_buf is not None:
                # int8 rows contract unscaled; the visiting tokens'
                # scales sit on the lanes and multiply the products.
                s = s * ks_buf[cur, hk:hk + 1, r0:r0 + t_loc]
            if soft_cap is not None:
                s = soft_cap * jnp.tanh(s / soft_cap)
            s = jnp.where(mask, s, _NEG_INF)
            m, l, acc = state[b][h]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            if vs_buf is not None:
                p = p * vs_buf[cur, hk:hk + 1, r0:r0 + t_loc]
            pv = jax.lax.dot_general(
                p, v_h, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            state[b][h] = (m_new, l, acc * alpha + pv)


def _ring_kernel(nbr_ref, q_ref, qpos_col_ref, k_ref, v_ref, kpos_ref,
                 *rest, sp, B, t_loc, Hq, Hkv, D, soft_cap, quant):
    """One program per shard: flash-fold the resident slot while the
    next hop's K/V (+positions, +scales) RDMAs into the other slot."""
    from jax.experimental.pallas import tpu as pltpu

    if quant:
        ks_ref, vs_ref, o_ref = rest[0], rest[1], rest[2]
        scratch = rest[3:]
    else:
        ks_ref = vs_ref = None
        o_ref = rest[0]
        scratch = rest[1:]
    (k_buf, v_buf, pos_buf, ks_buf, vs_buf, load_sem, send_sem,
     recv_sem, ack_sem) = scratch

    right = nbr_ref[0]
    left = nbr_ref[1]
    G = Hq // Hkv

    streams = [(k_ref, k_buf), (v_ref, v_buf), (kpos_ref, pos_buf)]
    if quant:
        streams += [(ks_ref, ks_buf), (vs_ref, vs_buf)]

    if sp > 1:
        # Neighbor barrier: no shard may RDMA into a peer that hasn't
        # entered the kernel and allocated these buffers.
        bsem = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(bsem, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(bsem, inc=1, device_id=right,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(bsem, 2)

    # Stage the local block into slot 0 (HBM -> VMEM).
    loads = [pltpu.make_async_copy(src, buf.at[0], load_sem.at[i])
             for i, (src, buf) in enumerate(streams)]
    for cp in loads:
        cp.start()
    for cp in loads:
        cp.wait()

    zero = jnp.zeros((t_loc, 1), jnp.float32)
    state = [[(jnp.full((t_loc, 1), _NEG_INF, jnp.float32), zero,
               jnp.zeros((t_loc, D), jnp.float32))
              for _ in range(Hq)] for _ in range(B)]

    for step in range(sp):
        cur, nxt = step % 2, (step + 1) % 2
        rdmas = []
        if step + 1 < sp:
            if step >= 1:
                # Credit: the receiver read slot `nxt` for the last
                # time at step-1; only its ack makes overwriting safe.
                pltpu.semaphore_wait(ack_sem, 1)
            # Ship the NEXT hop before any compute — the whole point.
            for i, (_, buf) in enumerate(streams):
                rdma = pltpu.make_async_remote_copy(
                    src_ref=buf.at[cur], dst_ref=buf.at[nxt],
                    send_sem=send_sem.at[i, cur],
                    recv_sem=recv_sem.at[i, nxt],
                    device_id=right,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                rdma.start()
                rdmas.append(rdma)
        _flash_fold(q_ref, qpos_col_ref, k_buf, v_buf, pos_buf,
                    ks_buf if quant else None,
                    vs_buf if quant else None, cur, state,
                    B=B, t_loc=t_loc, Hq=Hq, G=G, D=D,
                    soft_cap=soft_cap)
        if step + 1 < sp:
            if step <= sp - 3:
                # Slot `cur` is dead to us — credit the LEFT neighbor
                # (the device whose sends land in our buffers).
                pltpu.semaphore_signal(
                    ack_sem, inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
            for rdma in rdmas:
                rdma.wait()

    for b in range(B):
        r0 = b * t_loc
        for h in range(Hq):
            m, l, acc = state[b][h]
            # Fully-masked (padding) rows are junk-but-finite, exactly
            # as on the XLA ring — the divide guard matches it.
            o_ref[r0:r0 + t_loc, h * D:(h + 1) * D] = (
                acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def ring_flash_attention(
    q: jax.Array,            # [B, T_loc, Hq, D]
    k: jax.Array,            # [B, T_loc, Hkv, D] (int8 when k_scale given)
    v: jax.Array,            # [B, T_loc, Hkv, D]
    q_positions: jax.Array,  # [B, T_loc] absolute token positions
    kv_positions: Optional[jax.Array] = None,
    *,
    mesh,                    # the Mesh this shard_map body runs under
    axis_name: str = "sp",
    scale: Optional[float] = None,
    soft_cap: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,  # [B, T_loc, Hkv] f32
    v_scale: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash ring attention with RDMA'd K/V rotation; call inside
    `shard_map` with the T axis sharded over `axis_name`.  Drop-in for
    `ring_causal_attention` at eligible geometry (same signature modulo
    the static `mesh`); sp == 1 degenerates to plain flash attention
    with no remote traffic."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, t_loc, Hq, D = q.shape
    Hkv = k.shape[2]
    feat = Hkv * D
    if scale is None:
        scale = D ** -0.5
    if kv_positions is None:
        kv_positions = q_positions
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    sp = mesh.shape[axis_name]
    quant = k_scale is not None
    if not ring_kernel_supported(feat, t_loc, B, Hq, D, interpret):
        raise ValueError(
            f"ring kernel geometry rejected: per-shard feat={feat} and "
            f"t_local={t_loc} must be multiples of 128 and the chunk "
            f"must fit VMEM (modelled "
            f"{ring_vmem_bytes(t_loc, B, Hq, D)} of {_VMEM_BUDGET} "
            "bytes) — dispatch the XLA ppermute ring "
            "(ops/ring_attention.ring_causal_attention) instead")

    # Flattened LOGICAL ids of the ring neighbors: row-major over the
    # mesh axes in binding order, the layout make_mesh gives the device
    # array (and the flattening the interpret discharge rule mirrors).
    names = list(mesh.axis_names)
    flat = jnp.int32(0)
    for n in names:
        flat = flat * mesh.shape[n] + jax.lax.axis_index(n)
    stride = 1
    for n in names[names.index(axis_name) + 1:]:
        stride *= mesh.shape[n]
    idx = jax.lax.axis_index(axis_name)
    right = flat + ((idx + 1) % sp - idx) * stride
    left = flat + ((idx + sp - 1) % sp - idx) * stride
    nbr = jnp.stack([right, left]).astype(jnp.int32)

    # 2D operand views; q pre-scaled in f32 exactly like the XLA ring's
    # `qg` (one multiply outside the hop loop).
    q2 = (q.astype(jnp.float32) * scale).reshape(B * t_loc, Hq * D)
    qpos_col = q_positions.reshape(B * t_loc, 1).astype(jnp.int32)
    k2 = k.reshape(B * t_loc, feat)
    v2 = v.reshape(B * t_loc, feat)
    kpos = kv_positions.astype(jnp.int32)
    args = [nbr, q2, qpos_col, k2, v2, kpos]
    if quant:
        # [Hkv, rows]: tokens on the lanes, so the scale streams DMA and
        # slice like the positions do (a [rows, Hkv] buffer's 8-wide
        # minor dim is under Mosaic's 128-lane tiling).
        args += [k_scale.reshape(B * t_loc, Hkv).astype(jnp.float32).T,
                 v_scale.reshape(B * t_loc, Hkv).astype(jnp.float32).T]

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), vmem, vmem,
                any_spec, any_spec, any_spec]
    if quant:
        in_specs += [any_spec, any_spec]
    n_streams = 5 if quant else 3
    scratch = [
        pltpu.VMEM((2, B * t_loc, feat), k.dtype),            # k_buf
        pltpu.VMEM((2, B * t_loc, feat), v.dtype),            # v_buf
        pltpu.VMEM((2, B, t_loc), jnp.int32),                 # pos_buf
        pltpu.VMEM((2, Hkv, B * t_loc), jnp.float32),         # ks_buf
        pltpu.VMEM((2, Hkv, B * t_loc), jnp.float32),         # vs_buf
        pltpu.SemaphoreType.DMA((n_streams,)),                # load
        pltpu.SemaphoreType.DMA((n_streams, 2)),              # send
        pltpu.SemaphoreType.DMA((n_streams, 2)),              # recv
        pltpu.SemaphoreType.REGULAR,                          # ack
    ]

    kernel = functools.partial(
        _ring_kernel, sp=sp, B=B, t_loc=t_loc, Hq=Hq, Hkv=Hkv, D=D,
        soft_cap=soft_cap, quant=quant)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B * t_loc, Hq * D), q.dtype),
        in_specs=in_specs,
        out_specs=vmem,
        scratch_shapes=scratch,
        interpret=pltpu.InterpretParams() if interpret else False,
        compiler_params=pltpu.CompilerParams(collective_id=1),
    )(*args)
    return out.reshape(B, t_loc, Hq, D)
