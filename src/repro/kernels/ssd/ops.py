"""jit'd SSD wrapper: kernel for intra-chunk, lax.scan for the state carry."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import use_pallas
from repro.kernels.ssd import ref
from repro.kernels.ssd.kernel import ssd_intra_chunk


@functools.partial(jax.jit, static_argnames=("chunk", "use_kernel", "interpret"))
def ssd(x, dt, A, B, C, chunk: int, *, use_kernel=None, interpret=False
        ) -> Tuple[jax.Array, jax.Array]:
    """Full chunked SSD matching repro.models.ssm.ssd_chunked_ref.
    x: (b,S,nh,hp); dt: (b,S,nh); A: (nh,); B,C: (b,S,N).
    Returns (y (b,S,nh,hp), final_state (b,nh,hp,N))."""
    b, S, nh, hp = x.shape
    N = B.shape[-1]
    Q = chunk
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // Q
    xg = x.reshape(b * nc, Q, nh, hp)
    dtg = dt.reshape(b * nc, Q, nh)
    Bg = B.reshape(b * nc, Q, N)
    Cg = C.reshape(b * nc, Q, N)

    if use_pallas(use_kernel, interpret):
        y_intra, state, L = ssd_intra_chunk(xg, dtg, A, Bg, Cg,
                                            interpret=interpret)
    else:
        y_intra, state, L = ref.ssd_intra_chunk_ref(xg, dtg, A, Bg, Cg)

    # inter-chunk carry (cheap, sequential): h_{c+1} = decay_c * h_c + state_c
    y_intra = y_intra.reshape(b, nc, Q, nh, hp)
    state = state.reshape(b, nc, nh, hp, N)
    L = L.reshape(b, nc, Q, nh)
    Cc = Cg.reshape(b, nc, Q, N).astype(jnp.float32)
    chunk_decay = jnp.exp(L[:, :, -1, :])                # (b,nc,nh)

    def step(h, inp):
        st, dec, Lc, Ck = inp
        y_int = jnp.einsum("btn,bhpn,bth->bthp", Ck, h, jnp.exp(Lc))
        return dec[:, :, None, None] * h + st, y_int

    h0 = jnp.zeros((b, nh, hp, N), jnp.float32)
    hF, y_inter = jax.lax.scan(
        step, h0, (jnp.moveaxis(state, 1, 0), jnp.moveaxis(chunk_decay, 1, 0),
                   jnp.moveaxis(L, 1, 0), jnp.moveaxis(Cc, 1, 0)))
    y_inter = jnp.moveaxis(y_inter, 0, 1)
    y = (y_intra + y_inter).reshape(b, Sp, nh, hp)[:, :S]
    return y.astype(x.dtype), hF
