"""Pure-jnp oracle for the held-experts kernel: every held expert over
every token, each output weighted by the token's gate for that expert (zero
where the token did not choose it). Same precision as the kernel: bf16
operands, f32 accumulation, the SwiGLU product rounded to the weights'
dtype before the down projection."""
import jax
import jax.numpy as jnp


def combine_weights(sel, gates, first_expert: int, n_held: int):
    """(E, T) float32: gates[t, j] where sel[t, j] == first_expert + e."""
    oh = (sel[..., None] - first_expert == jnp.arange(n_held))
    return jnp.einsum("tk,tke->et", gates.astype(jnp.float32),
                      oh.astype(jnp.float32))


def held_experts_ffn(x, sel, gates, w_gate, w_up, w_down, *,
                     first_expert: int = 0):
    """x (T, d); sel, gates (T, k); w_gate, w_up (E, d, F); w_down (E, F, d)
    -> (T, d) float32."""
    f32 = jnp.float32
    comb = combine_weights(sel, gates, first_expert, w_gate.shape[0])
    g = jnp.einsum("td,edf->etf", x, w_gate, preferred_element_type=f32)
    u = jnp.einsum("td,edf->etf", x, w_up, preferred_element_type=f32)
    h = (jax.nn.silu(g) * u).astype(w_down.dtype)
    y = jnp.einsum("etf,efd->etd", h, w_down, preferred_element_type=f32)
    return jnp.einsum("et,etd->td", comb, y,
                      precision=jax.lax.Precision.HIGHEST)
