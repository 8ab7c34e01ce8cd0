"""On-chip fused histogram collectives — Pallas TPU ring kernels.

The distributed training hot loop reduces each split's ``(f, B, 3)``
leaf-histogram partials across the ``data`` mesh axis.  The stock path is
a bare ``jax.lax.psum`` of the whole state, which XLA stages through
HBM.  This module keeps the per-tree collective in VMEM and on the
interconnect (SNIPPETS [1]–[3] are the exemplar ring kernels).  On the
four-chip v5e host it does not beat psum at any payload timed (PERF.md
Findings, PR 29; ROADMAP D2a):

``ring_allreduce``
    Chunked ring reduce-scatter + all-gather of any float32 array, as one
    Pallas kernel: the array is split into one chunk per device, and at
    every step the remote DMA of the finished chunk overlaps the VPU
    accumulation of the next (double-buffered comm slots, explicit DMA
    send/recv semaphores).  At D = 2 the rotation-invariance of pairwise
    float adds makes the result BIT-IDENTICAL to ``lax.psum``; at D > 2
    each chunk's reduction visits devices in rotated ring order, so
    results differ from psum by ulp-level rounding only.

``ring_allreduce_select``
    The voted-column ring (ISSUE 16): gather ONLY the PV-Tree voted
    candidate columns — the ``(k2, B, 3)`` slab out of the full
    ``(f, B, 3)`` local histogram — and run the slab through the same
    chunked double-buffered ring schedule.  On wide data this cuts the
    collective *payload* 10–100× on top of the transport win: the
    reduce moves ``k2/f`` of the dense bytes.  The gather happens
    outside the kernel (a plain XLA take), so the ring kernel itself is
    shared with ``ring_allreduce`` — only the Mosaic collective id
    differs, keeping the two launches' barriers from aliasing when one
    program runs both.

Semantics are pinned on CPU via Pallas interpret mode (remote DMAs
discharge to ``all_gather`` exchanges on a forced multi-device host
platform), which is how tier-1 tests hold without a chip; the interpret
discharge supports a single named mesh axis, so the ring path runs on a
data-only ``Mesh((D,), ("data",))`` (gbdt/distributed.py builds one when
``collective="ring"`` resolves).  On TPU the kernels go through Mosaic
and a compile failure raises out of the fit with the compiler's message;
PERF.md "Bring-up on v5e" records which kernels Mosaic accepts.  See
docs/collectives.md for the kernel layout and knobs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.backend import pallas_interpret

#: VMEM gate for the dense ring all-reduce: the flattened array plus the
#: double-buffered work/comm chunks must stay resident (the output
#: aliases the input), so arrays beyond this fall back to ``lax.psum``.
#: (f=50, B=256, 3ch) f32 is 150 KB; the gate admits every realistic
#: histogram state while refusing pathological f that would thrash VMEM.
RING_MAX_BYTES = 4 << 20

#: Mosaic collective ids for the kernel families (any constant works
#: as long as every device in the gang runs the same program; distinct
#: ids keep the kernels' barriers from aliasing).
_RING_COLLECTIVE_ID = 7
_SELECT_RING_COLLECTIVE_ID = 9


def _chunk(c, cb: int):
    """Rows of chunk ``c`` (``cb`` rows each) along a ref's first axis."""
    return pl.ds(pl.multiple_of(c * cb, cb), cb)


def _neighbor_barrier(left, right):
    """Handshake with both ring neighbours before the first remote DMA.

    A remote copy lands in the neighbour's VMEM scratch and signals its
    semaphores, which are only this kernel's once the neighbour has
    entered it (and left whatever ran before).  The barrier semaphore is
    the one Mosaic allocates per ``collective_id``, so it is safe to
    signal a device that has not arrived yet."""
    sem = pltpu.get_barrier_semaphore()
    for nb in (left, right):
        pltpu.semaphore_signal(sem, inc=1, device_id=nb,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(sem, 2)


def _ring_schedule(out_ref, work, comm, send_sem, recv_sem, cap_sem, *,
                   axis_name: str, num_dev: int, cb: int, load, accumulate,
                   interpret: bool):
    """The ring all-reduce schedule both kernels share: D-1 reduce-
    scatter steps, then D-1 all-gather steps, as ``2(D-1)`` numbered
    transfers ``t``.  Transfer ``t`` sends from slot ``t % 2`` into the
    right neighbour's ``comm[(t + 1) % 2]``.

    ``load(chunk_idx, slot)`` fills ``work[slot]`` with this device's
    contribution to a chunk (it runs while the previous chunk is on the
    wire); ``accumulate(slot)`` does ``work[slot] += comm[slot]``.

    Reduce-scatter: at step ``s`` the accumulated chunk ``(my_id - s) %
    D`` goes right while chunk ``(my_id - s - 1) % D`` is loaded; after
    the last step device ``i`` holds the fully reduced chunk ``(i + 1) %
    D``.  All-gather: D-1 forwarding steps hand the reduced chunks round.

    Flow control.  With more than two devices the ring is NOT lockstep:
    a device's progress is gated by its left neighbour only, so it can
    run several steps ahead of its right neighbour and overwrite a comm
    slot the neighbour has not read yet.  ``cap_sem[k]`` counts the
    right neighbour's free ``comm[k]``: a receiver signals its LEFT
    neighbour once it is done with a slot (after the accumulate, or for a
    forwarded all-gather chunk after the forward left), and a sender
    waits for that credit before every write to a slot but the first.
    Each slot ends one credit up, drained before exit so the semaphores
    leave the kernel at zero.  ``interpret=True`` (the state-discharge
    interpreter) turns every DMA into an SPMD exchange with nothing in
    flight and implements no remote signal, so it alone runs without the
    barrier and the credits; Mosaic and the threaded TPU interpreter
    (``pltpu.InterpretParams``) run them.
    """
    my_id = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(my_id + 1, num_dev)
    left = jax.lax.rem(my_id + num_dev - 1, num_dev)
    sync = interpret is not True

    def free_slot(k):
        if sync:
            pltpu.semaphore_signal(
                cap_sem.at[k], inc=1, device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL)

    if sync:
        _neighbor_barrier(left, right)

    rs_steps = num_dev - 1
    last = 2 * rs_steps - 1
    load(my_id, 0)
    for t in range(last + 1):
        src_k, dst_k = t % 2, (t + 1) % 2
        # the first all-gather step forwards the chunk the reduce-scatter
        # left in work; later ones forward what the previous step received
        src = work if t <= rs_steps else comm
        if sync and t >= 2:
            pltpu.semaphore_wait(cap_sem.at[dst_k], 1)
        copy = pltpu.make_async_remote_copy(
            src_ref=src.at[src_k], dst_ref=comm.at[dst_k],
            send_sem=send_sem.at[src_k], recv_sem=recv_sem.at[dst_k],
            device_id=right, device_id_type=pltpu.DeviceIdType.LOGICAL)
        copy.start()
        if t < rs_steps:
            # overlap: this device's share of the next chunk while the
            # finished chunk is on the wire
            load(jax.lax.rem(my_id - (t + 1) + num_dev, num_dev), dst_k)
            copy.wait()
            accumulate(dst_k)
            free_slot(dst_k)
            if t == rs_steps - 1:
                out_ref[_chunk(right, cb)] = work[dst_k]
        else:
            copy.wait()
            s = t - rs_steps
            out_ref[_chunk(jax.lax.rem(my_id - s + num_dev, num_dev),
                           cb)] = comm[dst_k]
            if t > rs_steps:
                free_slot(src_k)       # forwarded: the sender may reuse it
            if t == last:
                free_slot(dst_k)
    if sync:
        for k in range(2):
            pltpu.semaphore_wait(cap_sem.at[k], 1)


def _ring_scratch(chunk_shape, dtype):
    """work + comm double buffers and the schedule's semaphores."""
    return [
        pltpu.VMEM((2, *chunk_shape), dtype),
        pltpu.VMEM((2, *chunk_shape), dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.REGULAR((2,)),
    ]


# -- dense ring all-reduce ---------------------------------------------------


def _ring_allreduce_kernel(x_ref, out_ref, work, comm, send_sem, recv_sem,
                           cap_sem, *, axis_name: str, num_dev: int,
                           interpret: bool):
    """Ring all-reduce of ``x_ref`` (D*cb, 128) into ``out_ref`` (which
    aliases it: every chunk of ``x_ref`` has been read by the time the
    reduce-scatter ends and the first output chunk is written)."""
    cb = x_ref.shape[0] // num_dev

    def load(c, slot):
        work[slot] = x_ref[_chunk(c, cb)]

    def accumulate(slot):
        work[slot] += comm[slot]

    _ring_schedule(out_ref, work, comm, send_sem, recv_sem, cap_sem,
                   axis_name=axis_name, num_dev=num_dev, cb=cb, load=load,
                   accumulate=accumulate, interpret=interpret)


def _ring_flat(x: jnp.ndarray, axis_name: str, num_devices: int,
               interpret: bool, collective_id: int) -> jnp.ndarray:
    """Shared launcher for the dense/select ring: flatten, pad to one
    (cb, 128) chunk per device, run :func:`_ring_allreduce_kernel` under
    the given Mosaic collective id, unpad."""
    shape, dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    total = flat.shape[0]
    rows = -(-total // 128)
    # whole (8, 128) f32 tiles per chunk: the kernel slices chunks at a
    # dynamic sublane offset, which Mosaic wants tile-aligned
    cb = 8 * -(-rows // (8 * num_devices))
    pad = num_devices * cb * 128 - total
    if pad:
        flat = jnp.pad(flat, (0, pad))
    arr = flat.reshape(num_devices * cb, 128)
    out = pl.pallas_call(
        functools.partial(_ring_allreduce_kernel, axis_name=axis_name,
                          num_dev=num_devices, interpret=interpret),
        out_shape=jax.ShapeDtypeStruct(arr.shape, jnp.float32),
        scratch_shapes=_ring_scratch((cb, 128), jnp.float32),
        input_output_aliases={0: 0},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
    )(arr)
    return out.reshape(-1)[:total].reshape(shape).astype(dtype)


def ring_allreduce(x: jnp.ndarray, axis_name: str, num_devices: int,
                   interpret: bool = False) -> jnp.ndarray:
    """Pallas ring all-reduce of ``x`` over ``axis_name`` (call inside
    ``shard_map`` on a SINGLE-named-axis mesh).  Drop-in for
    ``jax.lax.psum(x, axis_name)``; bit-identical at ``num_devices=2``,
    ulp-rotated at larger rings.  Raises when the VMEM gate refuses the
    array — trace-safe callers use :func:`ring_allreduce_or_psum`."""
    if num_devices <= 1:
        return x
    if 4 * int(np.prod(x.shape)) > RING_MAX_BYTES:
        raise ValueError(
            f"ring_allreduce: {x.shape} f32 exceeds the "
            f"{RING_MAX_BYTES >> 20} MB VMEM-residency gate")
    return _ring_flat(x, axis_name, num_devices, interpret,
                      _RING_COLLECTIVE_ID)


def ring_allreduce_or_psum(x: jnp.ndarray, axis_name: str,
                           num_devices: int) -> jnp.ndarray:
    """psum replacement for the grower: the ring kernel when the VMEM
    gate admits the array, ``lax.psum`` otherwise (a fact about the
    request, not about the compiler)."""
    if num_devices > 1 and 4 * int(np.prod(x.shape)) <= RING_MAX_BYTES:
        return ring_allreduce(x, axis_name, num_devices,
                              interpret=pallas_interpret())
    return jax.lax.psum(x, axis_name)


# -- voted-column ring: gather the candidate slab, ring only the slab --------


def _gather_cand(hist: jnp.ndarray, cand: jnp.ndarray) -> jnp.ndarray:
    """Gather the voted candidate columns: ``(f, B, 3)[cand (k2,)]`` →
    ``(k2, B, 3)``, or the batched-frontier layout ``(m, f, B, 3)`` with
    ``cand (m, k2)`` → ``(m, k2, B, 3)`` (m children share one launch)."""
    if cand.ndim == 1:
        return jnp.take(hist, cand, axis=0)
    return jnp.take_along_axis(hist, cand[:, :, None, None], axis=1)


def ring_allreduce_select(hist: jnp.ndarray, cand: jnp.ndarray,
                          axis_name: str, num_devices: int,
                          interpret: bool = False) -> jnp.ndarray:
    """Voted-column ring all-reduce (PV-Tree candidate reduction).

    Gathers ``hist[cand]`` — the ``(k2, B, 3)`` voted-candidate slab of
    a shard-LOCAL ``(f, B, 3)`` histogram, or the stacked ``(m, k2, B,
    3)`` slab of a batched frontier — and runs ONLY the slab through the
    chunked double-buffered ring schedule.  Same numerics contract as
    :func:`ring_allreduce` (bit-identical to gather+psum at D=2,
    ulp-rotated beyond), under its own Mosaic collective id so the dense
    and voted rings never share a barrier.  Raises when the VMEM gate
    refuses the slab — trace-safe callers use
    :func:`ring_allreduce_select_or_psum`."""
    slab = _gather_cand(hist, cand)
    if num_devices <= 1:
        return slab
    if 4 * int(np.prod(slab.shape)) > RING_MAX_BYTES:
        raise ValueError(
            f"ring_allreduce_select: slab {slab.shape} f32 exceeds the "
            f"{RING_MAX_BYTES >> 20} MB VMEM-residency gate")
    return _ring_flat(slab, axis_name, num_devices, interpret,
                      _SELECT_RING_COLLECTIVE_ID)


def ring_allreduce_select_or_psum(hist: jnp.ndarray, cand: jnp.ndarray,
                                  axis_name: str,
                                  num_devices: int) -> jnp.ndarray:
    """Voted-column reduction for the grower: the select-ring when the
    VMEM gate admits the slab, gather + ``lax.psum`` otherwise."""
    slab = _gather_cand(hist, cand)
    if num_devices > 1 and 4 * int(np.prod(slab.shape)) <= RING_MAX_BYTES:
        return _ring_flat(slab, axis_name, num_devices, pallas_interpret(),
                          _SELECT_RING_COLLECTIVE_ID)
    return jax.lax.psum(slab, axis_name)
