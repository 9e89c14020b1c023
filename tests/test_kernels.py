"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp
oracle (kernels/ref.py), plus ref-vs-model consistency checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_stacked)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.routing_decide import (routing_attain, routing_guard,
                                          routing_topk)
from repro.kernels.routing_score import build_erlang_table, routing_score
from repro.kernels.ssd_scan import ssd_scan

# Pallas-interpret / lowering sweeps run for minutes; CI smoke skips them.
pytestmark = pytest.mark.slow


def tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("b,s,h,hkv,d", [
        (1, 128, 1, 1, 64),      # minimal
        (2, 256, 4, 2, 64),      # GQA
        (2, 128, 4, 1, 32),      # MQA
        (1, 512, 2, 2, 128),     # MXU-aligned head dim
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_matches_ref(self, b, s, h, hkv, d, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, s, h, d), dtype)
        k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
        v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
        got = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64,
                              interpret=True)
        want = ref.attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol(dtype))

    @pytest.mark.parametrize("window", [32, 64, 100])
    def test_sliding_window(self, window):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (2, 256, 2, 32), jnp.float32)
        k = jax.random.normal(ks[1], (2, 256, 2, 32), jnp.float32)
        v = jax.random.normal(ks[2], (2, 256, 2, 32), jnp.float32)
        got = flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_kv=64, interpret=True)
        want = ref.attention(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def test_softcap_and_scale(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 128, 2, 64), jnp.float32) * 3
        k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.float32) * 3
        v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.float32)
        got = flash_attention(q, k, v, causal=True, softcap=30.0,
                              scale=0.1, block_q=64, block_kv=64,
                              interpret=True)
        want = ref.attention(q, k, v, causal=True, softcap=30.0, scale=0.1)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def test_non_causal(self):
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (2, 128, 2, 32), jnp.float32)
        k = jax.random.normal(ks[1], (2, 128, 2, 32), jnp.float32)
        v = jax.random.normal(ks[2], (2, 128, 2, 32), jnp.float32)
        got = flash_attention(q, k, v, causal=False, block_q=64,
                              block_kv=64, interpret=True)
        want = ref.attention(q, k, v, causal=False)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def test_ref_softmax_rows_sum_to_one_property(self):
        # oracle sanity: output of attention over constant V equals V
        v_const = jnp.ones((1, 64, 2, 16), jnp.float32) * 3.0
        ks = jax.random.split(jax.random.PRNGKey(4), 2)
        q = jax.random.normal(ks[0], (1, 64, 2, 16), jnp.float32)
        k = jax.random.normal(ks[1], (1, 64, 2, 16), jnp.float32)
        out = ref.attention(q, k, v_const, causal=True)
        np.testing.assert_allclose(out, v_const, atol=1e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("b,h,hkv,d,c", [
        (1, 1, 1, 32, 128),
        (3, 4, 2, 64, 256),
        (2, 8, 1, 64, 512),
        (2, 32, 4, 128, 256),   # Mellum2's groups: rep 8, one sublane tile
        (2, 12, 2, 64, 256),    # rep 6: groups start off the sublane tile
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, b, h, hkv, d, c, dtype):
        rng = np.random.default_rng(0)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, h, d), dtype)
        k = jax.random.normal(ks[1], (b, c, hkv, d), dtype)
        v = jax.random.normal(ks[2], (b, c, hkv, d), dtype)
        kv_pos = jnp.asarray(rng.integers(-1, 300, (b, c)), jnp.int32)
        q_pos = jnp.asarray(rng.integers(100, 301, (b,)), jnp.int32)
        got = decode_attention(q, k, v, kv_pos, q_pos, block_kv=64,
                               interpret=True)
        want = ref.decode_attention(q, k, v, kv_pos, q_pos)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol(dtype))

    @pytest.mark.parametrize("h,hkv", [(4, 4), (8, 2), (32, 4), (12, 2)])
    def test_one_dot_pair_per_kv_head(self, h, hkv):
        """A KV head's query group attends as one score dot and one value
        dot: the kernel body holds 2 x Hkv dots, whatever the group."""
        s = jax.ShapeDtypeStruct
        kv = s((2, 128, hkv, 128), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(
            lambda *a: decode_attention(*a, block_kv=64))(
                s((2, h, 64), jnp.bfloat16), kv, kv, s((2, 128), jnp.int32),
                s((2,), jnp.int32))
        assert str(jaxpr).count("dot_general") == 2 * hkv

    def test_window(self):
        rng = np.random.default_rng(1)
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        b, h, hkv, d, c = 2, 4, 2, 32, 256
        q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, c, hkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, c, hkv, d), jnp.float32)
        kv_pos = jnp.asarray(rng.integers(0, 500, (b, c)), jnp.int32)
        q_pos = jnp.asarray([400, 499], jnp.int32)
        got = decode_attention(q, k, v, kv_pos, q_pos, window=128,
                               block_kv=64, interpret=True)
        want = ref.decode_attention(q, k, v, kv_pos, q_pos, window=128)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def test_ring_buffer_semantics(self):
        """Cache equals an explicit suffix window -> same result as full
        attention restricted to those positions."""
        b, h, d, c = 1, 2, 16, 64
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, c, h, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, c, h, d), jnp.float32)
        # slots hold positions 100..163 (no wraparound ambiguity)
        kv_pos = jnp.arange(100, 164, dtype=jnp.int32)[None, :]
        q_pos = jnp.asarray([163], jnp.int32)
        got = decode_attention(q, k, v, kv_pos, q_pos, interpret=True,
                               block_kv=64)
        # equivalent full attention with q appended at the end
        q4 = q[:, None, :, :]
        out = ref.attention(q4, k, v, causal=True)
        np.testing.assert_allclose(got, out[:, 0], atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("layer", [0, 2, 4])
    @pytest.mark.parametrize("h,hkv,window,q_pos,w", [
        (4, 4, 0, [300, 517], 32),     # global; both rings wrapped (C=128)
        (4, 4, 48, [300, 517], 32),    # sliding window over wrapped rings
        (8, 2, 0, [90, 517], 32),      # GQA, rep 4; one ring not yet full
        (8, 2, 48, [90, 517], 128),    # rows padded to a 128-lane tile
        (32, 4, 48, [300, 517], 128),  # Mellum2's rep 8, sliding layer
        (32, 4, 0, [90, 517], 128),    # rep 8, full layer, one ring part-full
    ])
    def test_stacked_matches_ref_on_the_layer(self, layer, h, hkv, window,
                                              q_pos, w):
        """The stacked entry attends layer ``layer`` of an (L, B, C, Hkv,
        W) stack as the oracle attends the first D lanes of that layer's
        slab, and as the stacked oracle does. Layer l lags the query by l
        positions, so a wrong layer's positions differ as well as its
        keys and values."""
        n_layers, b, d, c = 5, 2, 32, 128
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (n_layers, b, c, hkv, w), jnp.float32)
        v = jax.random.normal(ks[2], (n_layers, b, c, hkv, w), jnp.float32)
        qp = jnp.asarray(q_pos, jnp.int32)
        # slot j holds the newest position = j (mod C) the layer has
        # written, -1 where it has written none
        newest = qp[None, :, None] - jnp.arange(n_layers)[:, None, None]
        kv_pos = newest - (newest - jnp.arange(c)) % c
        kv_pos = jnp.where(kv_pos >= 0, kv_pos, -1).astype(jnp.int32)
        got = decode_attention_stacked(q, k, v, kv_pos, qp,
                                       jnp.int32(layer), window=window,
                                       block_kv=64, interpret=True)
        want = ref.decode_attention(q, k[layer, ..., :d], v[layer, ..., :d],
                                    kv_pos[layer], qp, window=window)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        np.testing.assert_array_equal(
            ref.decode_attention_stacked(q, k, v, kv_pos, qp,
                                         jnp.int32(layer), window=window),
            want)


class TestSSDScan:
    @pytest.mark.parametrize("b,l,h,p,g,n,chunk", [
        (1, 64, 1, 16, 1, 8, 16),
        (2, 128, 4, 32, 2, 16, 32),
        (2, 128, 4, 32, 4, 16, 64),
        (1, 256, 2, 64, 1, 32, 64),
    ])
    def test_matches_sequential_oracle(self, b, l, h, p, g, n, chunk):
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        x = jax.random.normal(ks[0], (b, l, h, p), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
        a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
        bb = jax.random.normal(ks[3], (b, l, g, n)) * 0.3
        cc = jax.random.normal(ks[4], (b, l, g, n)) * 0.3
        d_skip = jax.random.normal(ks[5], (h,))
        got, hf = ssd_scan(x, dt, a, bb, cc, d_skip, chunk=chunk,
                           interpret=True, return_final_state=True)
        want, hf_want = ref.ssd_scan(x, dt, a, bb, cc, d_skip,
                                     return_final_state=True)
        np.testing.assert_allclose(got, want, atol=5e-4, rtol=5e-4)
        np.testing.assert_allclose(hf, hf_want, atol=5e-4, rtol=5e-4)

    def test_initial_state_continuation(self):
        """Scanning [first half] then [second half with carried state]
        equals scanning the whole sequence (the prefill->decode contract)."""
        b, l, h, p, g, n = 1, 128, 2, 16, 1, 8
        ks = jax.random.split(jax.random.PRNGKey(1), 6)
        x = jax.random.normal(ks[0], (b, l, h, p), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
        a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
        bb = jax.random.normal(ks[3], (b, l, g, n)) * 0.3
        cc = jax.random.normal(ks[4], (b, l, g, n)) * 0.3
        d_skip = jnp.zeros((h,))
        full = ref.ssd_scan(x, dt, a, bb, cc, d_skip)
        half = l // 2
        y1, h1 = ssd_scan(x[:, :half], dt[:, :half], a, bb[:, :half],
                          cc[:, :half], d_skip, chunk=32, interpret=True,
                          return_final_state=True)
        y2 = ssd_scan(x[:, half:], dt[:, half:], a, bb[:, half:],
                      cc[:, half:], d_skip, initial_state=h1, chunk=32,
                      interpret=True)
        np.testing.assert_allclose(
            jnp.concatenate([y1, y2], axis=1), full, atol=5e-4, rtol=5e-4)


class TestRoutingScore:
    def _setup(self, i=6, r=256, seed=0):
        rng = np.random.default_rng(seed)
        p = dict(
            alpha=jnp.asarray(rng.uniform(0.1, 1.0, i), jnp.float32),
            beta=jnp.asarray(rng.uniform(0.1, 2.0, i), jnp.float32),
            gamma=jnp.asarray(rng.uniform(0.9, 1.8, i), jnp.float32),
            mu=jnp.asarray(rng.uniform(0.5, 3.0, i), jnp.float32),
            n=jnp.asarray(rng.integers(1, 8, i), jnp.float32),
            rtt=jnp.asarray(rng.uniform(0, 0.1, i), jnp.float32),
            slo=jnp.asarray(rng.uniform(1.0, 4.0, i), jnp.float32),
            cost=jnp.asarray(rng.uniform(1, 3, i), jnp.float32),
        )
        lam = jnp.asarray(rng.uniform(0.0, 10.0, r), jnp.float32)
        table = build_erlang_table(np.asarray(p["mu"]), np.asarray(p["n"]))
        return lam, p, table

    @pytest.mark.parametrize("i,r", [(2, 64), (6, 256), (11, 128)])
    def test_matches_ref(self, i, r):
        lam, p, table = self._setup(i, r, seed=i)
        gi, gg, gok = routing_score(lam, *p.values(), table, block_r=64,
                                    interpret=True)
        ri, rg, rok = ref.routing_score(lam, *p.values(), table)
        assert bool(jnp.all(gok == rok))
        feas = np.asarray(rok)
        np.testing.assert_array_equal(np.asarray(gi)[feas],
                                      np.asarray(ri)[feas])
        np.testing.assert_allclose(np.asarray(gg)[feas],
                                   np.asarray(rg)[feas], rtol=1e-4)

    @pytest.mark.parametrize("i,r", [(3, 64), (6, 128)])
    def test_matches_ref_per_request_slo_rows(self, i, r):
        """(R, I) SLO rows (explicit req.slo / lane exclusions as -1)
        route identically through the kernel and the ref oracle — the
        ROADMAP open item that used to force a vmap fallback."""
        lam, p, table = self._setup(i, r, seed=100 + i)
        rng = np.random.default_rng(100 + i)
        slo_rows = rng.uniform(0.5, 4.0, (r, i)).astype(np.float32)
        # a sprinkling of lane exclusions: slo = -1 marks the candidate
        # infeasible for that request (g >= 0 always)
        slo_rows[rng.uniform(size=(r, i)) < 0.2] = -1.0
        p = dict(p, slo=jnp.asarray(slo_rows))
        gi, gg, gok = routing_score(lam, *p.values(), table, block_r=32,
                                    interpret=True)
        ri, rg, rok = ref.routing_score(lam, *p.values(), table)
        assert bool(jnp.all(gok == rok))
        feas = np.asarray(rok)
        assert feas.any() and not feas.all()   # both regimes exercised
        np.testing.assert_array_equal(np.asarray(gi)[feas],
                                      np.asarray(ri)[feas])
        np.testing.assert_allclose(np.asarray(gg)[feas],
                                   np.asarray(rg)[feas], rtol=1e-4)

    def test_per_request_rows_match_shared_slo(self):
        """Broadcasting the shared (I,) budget into identical (R, I)
        rows must not change any decision."""
        lam, p, table = self._setup(4, 64, seed=3)
        i1, g1, ok1 = ref.routing_score(lam, *p.values(), table)
        rows = jnp.broadcast_to(p["slo"][None, :], (64, 4))
        p2 = dict(p, slo=rows)
        i2, g2, ok2 = ref.routing_score(lam, *p2.values(), table)
        assert bool(jnp.all(ok1 == ok2)) and bool(jnp.all(i1 == i2))
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))

    def test_matches_router_scalar_path(self):
        """Kernel ref agrees with the (numpy) router used by the
        simulator, up to the table-interpolation error."""
        from repro.core.router import score_instances_np
        lam, p, table = self._setup(4, 64, seed=7)
        _, rg, rok = ref.routing_score(lam, *p.values(), table)
        for ridx in range(0, 64, 7):
            g_np = score_instances_np(
                float(lam[ridx]), np.asarray(p["alpha"]),
                np.asarray(p["beta"]), np.asarray(p["gamma"]),
                np.asarray(p["mu"]), np.asarray(p["n"]),
                np.asarray(p["rtt"]))
            feasible = (g_np <= np.asarray(p["slo"])) & (g_np < 1e8)
            if feasible.any() and bool(rok[ridx]):
                best = g_np[feasible].min()
                assert abs(float(rg[ridx]) - best) / best < 0.05


def _routing_setup(i, r, seed):
    """Seeded candidate table + request rows for the fused decision
    kernels (the TestRoutingScore idiom, plus guard columns)."""
    rng = np.random.default_rng(seed)
    p = dict(
        alpha=jnp.asarray(rng.uniform(0.1, 1.0, i), jnp.float32),
        beta=jnp.asarray(rng.uniform(0.1, 2.0, i), jnp.float32),
        gamma=jnp.asarray(rng.uniform(0.9, 1.8, i), jnp.float32),
        mu=jnp.asarray(rng.uniform(0.5, 3.0, i), jnp.float32),
        n=jnp.asarray(rng.integers(1, 8, i), jnp.float32),
        rtt=jnp.asarray(rng.uniform(0, 0.1, i), jnp.float32),
    )
    lam = jnp.asarray(rng.uniform(0.0, 10.0, r), jnp.float32)
    table = build_erlang_table(np.asarray(p["mu"]), np.asarray(p["n"]))
    return rng, lam, p, table


class TestRoutingGuard:
    """Fused Algorithm-1 guard kernel vs its ref.routing_guard oracle."""

    @pytest.mark.parametrize("i,r", [(2, 64), (6, 256), (11, 128)])
    def test_matches_ref(self, i, r):
        rng, lam, p, table = _routing_setup(i, r, seed=20 + i)
        tau = jnp.asarray(rng.uniform(0.1, 3.0, r), jnp.float32)
        home = jnp.asarray(rng.integers(0, i, r), jnp.int32)
        up = jnp.asarray(rng.integers(-1, i, r), jnp.int32)
        gi, gg, goff = routing_guard(lam, *p.values(), tau, home, up,
                                     table, block_r=64, interpret=True)
        ri, rg, roff = ref.routing_guard(lam, *p.values(), tau, home, up,
                                        table)
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))
        np.testing.assert_array_equal(np.asarray(goff), np.asarray(roff))
        np.testing.assert_allclose(np.asarray(gg), np.asarray(rg),
                                   rtol=1e-4)

    def test_tau_boundary_is_strict_in_both(self):
        """Guard tau edge cases: lam = 0 makes g = alpha + rtt EXACTLY
        in both implementations (no table interpolation error), so the
        decision boundary can be pinned bitwise — tau == g_inst must NOT
        offload (strict >), one f32 ulp below must."""
        i, r = 3, 8
        _, _, p, table = _routing_setup(i, r, seed=5)
        lam = jnp.zeros(r, jnp.float32)
        home = jnp.asarray(np.arange(r) % i, jnp.int32)
        up = jnp.asarray((np.arange(r) + 1) % i, jnp.int32)
        a = np.asarray(p["alpha"]); rt = np.asarray(p["rtt"])
        h = np.asarray(home)
        g_inst = (a[h].astype(np.float32) + rt[h].astype(np.float32)
                  - rt[h].astype(np.float32))
        for tau_np, want_off in (
                (g_inst, False),                                   # == tau
                (np.nextafter(g_inst, np.float32(-1.0)), True)):   # 1 ulp
            tau = jnp.asarray(tau_np, jnp.float32)
            gi, _, goff = routing_guard(lam, *p.values(), tau, home, up,
                                        table, block_r=8, interpret=True)
            ri, _, roff = ref.routing_guard(lam, *p.values(), tau, home,
                                           up, table)
            assert bool(jnp.all(goff == want_off))
            assert bool(jnp.all(roff == want_off))
            np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))

    def test_top_tier_and_unstable_sentinel(self):
        """up = -1 never offloads no matter how hot the home pool; an
        unstable home (rho >= 1) carries the 1e9 sentinel with NO rtt
        stripped, so it offloads for any tau < 1e9 but not tau >= 1e9 —
        kernel and oracle must agree on all four corners."""
        i, r = 2, 8
        p = dict(
            alpha=jnp.asarray([0.1, 0.1], jnp.float32),
            beta=jnp.asarray([0.1, 0.1], jnp.float32),
            gamma=jnp.asarray([1.0, 1.0], jnp.float32),
            mu=jnp.asarray([0.01, 100.0], jnp.float32),  # col 0 unstable
            n=jnp.asarray([1.0, 1.0], jnp.float32),
            rtt=jnp.asarray([0.01, 0.02], jnp.float32),
        )
        table = build_erlang_table(np.asarray(p["mu"]), np.asarray(p["n"]))
        lam = jnp.full(r, 5.0, jnp.float32)        # rho(col 0) >> 1
        home = jnp.zeros(r, jnp.int32)
        up = jnp.asarray([1, -1] * (r // 2), jnp.int32)
        tau = jnp.asarray([0.5, 0.5, 1e9, 1e9] * (r // 4), jnp.float32)
        gi, gg, goff = routing_guard(lam, *p.values(), tau, home, up,
                                     table, block_r=8, interpret=True)
        ri, rg, roff = ref.routing_guard(lam, *p.values(), tau, home, up,
                                        table)
        # offload ONLY where an upstream exists and tau < sentinel
        want = np.array([True, False, False, False] * (r // 4))
        np.testing.assert_array_equal(np.asarray(goff), want)
        np.testing.assert_array_equal(np.asarray(roff), want)
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))
        # the stayed-home rows report the sentinel, not a finite g
        assert float(np.asarray(gg)[1]) == 1e9 == float(np.asarray(rg)[1])


class TestRoutingTopK:
    """Fused top-k select kernel vs its ref.routing_topk oracle."""

    def _slo_cost(self, rng, i):
        return (jnp.asarray(rng.uniform(1.0, 4.0, i), jnp.float32),
                jnp.asarray(rng.uniform(1, 3, i), jnp.float32))

    @pytest.mark.parametrize("i,r", [(2, 64), (6, 256), (11, 128)])
    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_matches_ref(self, i, r, k):
        rng, lam, p, table = _routing_setup(i, r, seed=40 + i)
        slo, cost = self._slo_cost(rng, i)
        gi, gg, gok = routing_topk(lam, *p.values(), slo, cost, table,
                                   k=k, block_r=64, interpret=True)
        ri, rg, rok = ref.routing_topk(lam, *p.values(), slo, cost, table,
                                      k=k)
        np.testing.assert_array_equal(np.asarray(gok), np.asarray(rok))
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))
        np.testing.assert_allclose(np.asarray(gg), np.asarray(rg),
                                   rtol=1e-4, atol=1e-5)

    def test_margin_gates_duplicates(self):
        rng, lam, p, table = _routing_setup(5, 64, seed=77)
        slo, cost = self._slo_cost(rng, 5)
        for margin in (0.0, 0.5, 2.0):
            gi, _, _ = routing_topk(lam, *p.values(), slo, cost, table,
                                    k=3, margin=margin, block_r=32,
                                    interpret=True)
            ri, _, _ = ref.routing_topk(lam, *p.values(), slo, cost,
                                       table, k=3, margin=margin)
            np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))

    def test_all_infeasible_rows(self):
        """Row with no feasible candidate: idx column 0 is -1 (the
        policies substitute their upstream fallback), duplicate columns
        empty, g column 0 the row-min predicted score."""
        rng, lam, p, table = _routing_setup(4, 32, seed=9)
        slo = jnp.full(4, 1e-6, jnp.float32)     # nothing meets this
        cost = jnp.asarray(rng.uniform(1, 3, 4), jnp.float32)
        gi, gg, gok = routing_topk(lam, *p.values(), slo, cost, table,
                                   k=3, block_r=32, interpret=True)
        ri, rg, rok = ref.routing_topk(lam, *p.values(), slo, cost, table,
                                      k=3)
        assert not bool(jnp.any(gok)) and not bool(jnp.any(rok))
        assert bool(jnp.all(gi == -1)) and bool(jnp.all(ri == -1))
        np.testing.assert_allclose(np.asarray(gg)[:, 0],
                                   np.asarray(rg)[:, 0], rtol=1e-4)

    def test_k_exceeds_feasible_count(self):
        """k larger than the feasible set: the extra columns are -1 in
        kernel and oracle alike (per-request SLO rows leave exactly two
        candidates feasible)."""
        rng, lam, p, table = _routing_setup(5, 32, seed=13)
        cost = jnp.asarray(rng.uniform(1, 3, 5), jnp.float32)
        slo_rows = np.full((32, 5), -1.0, np.float32)
        slo_rows[:, 1] = 100.0
        slo_rows[:, 3] = 100.0                   # cols 1 and 3 feasible
        gi, _, gok = routing_topk(lam, *p.values(), jnp.asarray(slo_rows),
                                  cost, table, k=5, block_r=32,
                                  interpret=True)
        ri, _, rok = ref.routing_topk(lam, *p.values(),
                                     jnp.asarray(slo_rows), cost, table,
                                     k=5)
        assert bool(jnp.all(gok)) and bool(jnp.all(rok))
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))
        got = np.asarray(gi)
        # primaries come from the two admitted columns; the duplicate
        # column holds the other one where it is still feasible (a hot
        # window can saturate it), -1 otherwise
        assert set(got[:, 0]) <= {1, 3}
        assert set(got[:, 1]) <= {-1, 1, 3}
        np.testing.assert_array_equal(got[:, 2:], -1)

    def test_f32_tie_break_lowest_index_wins(self):
        """Bit-identical candidates (clones) produce bit-equal g, so the
        primary must be the cheapest near-tie and the duplicate order
        strictly index-ascending — first-occurrence argmin semantics in
        kernel and oracle."""
        i, r = 4, 32
        one = lambda v: jnp.full(i, v, jnp.float32)
        p = dict(alpha=one(0.2), beta=one(0.3), gamma=one(1.2),
                 mu=one(2.0), n=one(2.0), rtt=one(0.01))
        table = build_erlang_table(np.asarray(p["mu"]), np.asarray(p["n"]))
        lam = jnp.asarray(np.linspace(0.0, 3.0, r), jnp.float32)
        slo = one(5.0)
        cost = jnp.asarray([2.0, 1.0, 1.0, 2.0], jnp.float32)
        gi, _, _ = routing_topk(lam, *p.values(), slo, cost, table, k=4,
                                block_r=32, interpret=True)
        ri, _, _ = ref.routing_topk(lam, *p.values(), slo, cost, table,
                                   k=4)
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))
        got = np.asarray(gi)
        # cheapest near-tie: cost ties between cols 1/2 break to col 1
        np.testing.assert_array_equal(got[:, 0], 1)
        # duplicates ascend by index among the remaining clones
        np.testing.assert_array_equal(got[:, 1], 0)
        np.testing.assert_array_equal(got[:, 2], 2)
        np.testing.assert_array_equal(got[:, 3], 3)


class TestRoutingAttain:
    """Fused attainment-argmax kernel vs its ref.routing_attain oracle."""

    @pytest.mark.parametrize("i,r", [(2, 64), (6, 256), (11, 128)])
    @pytest.mark.parametrize("k", [1, 3])
    def test_matches_ref(self, i, r, k):
        rng, lam, p, table = _routing_setup(i, r, seed=60 + i)
        slo = jnp.asarray(rng.uniform(1.0, 4.0, i), jnp.float32)
        sigma = jnp.asarray(rng.uniform(0.05, 0.8, i), jnp.float32)
        avail = jnp.asarray(rng.uniform(0.7, 1.0, i), jnp.float32)
        gi, gg, gok = routing_attain(lam, *p.values(), slo, sigma, avail,
                                     table, k=k, margin=0.1, block_r=64,
                                     interpret=True)
        ri, rg, rok = ref.routing_attain(lam, *p.values(), slo, sigma,
                                        avail, table, k=k, margin=0.1)
        np.testing.assert_array_equal(np.asarray(gok), np.asarray(rok))
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))
        np.testing.assert_allclose(np.asarray(gg), np.asarray(rg),
                                   rtol=1e-4, atol=1e-5)

    def test_uniform_distribution_degrades_to_argmin_g(self):
        """Uniform sigma/avail make p strictly decreasing in g, so the
        attainment winner collapses to the latency argmin over the
        feasible set (computed directly from the oracle's score matrix)
        — and kernel == oracle exactly. The budget must be uniform too:
        a per-candidate slo reorders p away from the g order."""
        rng, lam, p, table = _routing_setup(5, 64, seed=88)
        slo = jnp.full(5, 3.0, jnp.float32)
        sigma = jnp.full(5, 0.3, jnp.float32)
        avail = jnp.full(5, 1.0, jnp.float32)
        ai, _, aok = routing_attain(lam, *p.values(), slo, sigma, avail,
                                    table, k=2, block_r=32, interpret=True)
        ri, _, _ = ref.routing_attain(lam, *p.values(), slo, sigma, avail,
                                     table, k=2)
        np.testing.assert_array_equal(np.asarray(ai), np.asarray(ri))
        g, rho = ref._table_scores(lam, p["alpha"], p["beta"], p["gamma"],
                                   p["mu"], p["n"], p["rtt"], table)
        g = np.asarray(g)
        feasible = np.asarray(rho < 1.0) & (g <= np.asarray(slo)[None, :])
        want = np.argmin(np.where(feasible, g, np.inf), axis=1)
        feas = np.asarray(aok)
        assert feas.any()
        np.testing.assert_array_equal(np.asarray(ri)[feas, 0], want[feas])

    def test_sigma_zero_is_a_step_function(self):
        """sigma <= 0 collapses the lognormal to a step at the SLO
        (slo_attain_prob edge semantics): p = avail inside the budget,
        0 outside — the argmax then ranks purely by avail, ties to
        lower g. Kernel and oracle must agree bitwise on indices."""
        rng, lam, p, table = _routing_setup(4, 64, seed=91)
        slo = jnp.asarray(rng.uniform(1.0, 4.0, 4), jnp.float32)
        sigma = jnp.zeros(4, jnp.float32)
        avail = jnp.asarray([0.9, 0.99, 0.99, 0.7], jnp.float32)
        gi, _, gok = routing_attain(lam, *p.values(), slo, sigma, avail,
                                    table, k=2, block_r=32, interpret=True)
        ri, _, rok = ref.routing_attain(lam, *p.values(), slo, sigma,
                                       avail, table, k=2)
        np.testing.assert_array_equal(np.asarray(gok), np.asarray(rok))
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))

    def test_all_infeasible_rows(self):
        rng, lam, p, table = _routing_setup(3, 32, seed=17)
        slo = jnp.full(3, 1e-6, jnp.float32)
        sigma = jnp.full(3, 0.2, jnp.float32)
        avail = jnp.ones(3, jnp.float32)
        gi, _, gok = routing_attain(lam, *p.values(), slo, sigma, avail,
                                    table, k=2, block_r=32, interpret=True)
        ri, _, rok = ref.routing_attain(lam, *p.values(), slo, sigma,
                                       avail, table, k=2)
        assert not bool(jnp.any(gok)) and not bool(jnp.any(rok))
        assert bool(jnp.all(gi == -1)) and bool(jnp.all(ri == -1))


class TestMoeGmm:
    # (rows, K, N, group sizes, row block): groups of every size with
    # empty ones between; every row on one expert; rows that fill no
    # whole block; one expert per block; a weight too wide for one VMEM
    # block (two lane blocks of N)
    @pytest.mark.parametrize("m,k,n,sizes,block_m", [
        (40, 128, 256, [10, 0, 25, 5], 16),
        (40, 128, 256, [0, 0, 40, 0], 16),
        (37, 128, 128, [3, 0, 0, 10, 4, 0, 6, 2], 16),
        (64, 256, 384, [16, 16, 16, 16], 16),
        (24, 2048, 2304, [0, 20, 4], 16),
    ])
    def test_matches_ref(self, m, k, n, sizes, block_m):
        kx, kw = jax.random.split(jax.random.PRNGKey(m + n))
        x = jax.random.normal(kx, (m, k), jnp.float32).astype(jnp.bfloat16)
        w = jax.random.normal(kw, (len(sizes), k, n),
                              jnp.float32).astype(jnp.bfloat16)
        sizes = jnp.asarray(sizes, jnp.int32)
        got = moe_gmm(x, w, sizes, block_m=block_m, interpret=True)
        want = ref.moe_gmm(x, w, sizes)
        # the same bf16 products accumulated in f32, rounded once to bf16
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))

    @pytest.mark.parametrize("layer", [0, 2])
    def test_layer_of_a_stack_matches_ref(self, layer):
        """The decode step's form: one layer's experts of a stack, the
        layer index a scalar-prefetch operand."""
        kx, kw = jax.random.split(jax.random.PRNGKey(layer))
        x = jax.random.normal(kx, (24, 128), jnp.float32)
        w = jax.random.normal(kw, (3, 4, 128, 256), jnp.float32)
        sizes = jnp.asarray([5, 0, 12, 7], jnp.int32)
        got = moe_gmm(x, w, sizes, jnp.int32(layer), interpret=True)
        want = ref.moe_gmm(x, w[layer], sizes)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **tol(jnp.float32))

    def test_ref_is_the_grouped_product(self):
        """Row r of the oracle is x[r] @ w[group of r]; rows past the
        groups are zero."""
        x = jax.random.normal(jax.random.PRNGKey(0), (9, 8), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 5), jnp.float32)
        got = np.asarray(ref.moe_gmm(x, w, jnp.asarray([3, 0, 4])))
        group = [0, 0, 0, 2, 2, 2, 2]
        want = np.stack([np.asarray(x[r]) @ np.asarray(w[g])
                         for r, g in enumerate(group)] + [np.zeros(5)] * 2)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_visits_skip_empty_experts(self):
        """Experts that received no row are never visited, so their
        weights are never read; every row block is visited."""
        from repro.kernels.moe_gmm import _visits
        tile, group, widx, offsets, n_real = _visits(
            jnp.asarray([0, 5, 0, 0, 11, 0], jnp.int32), 32, 16)
        n = int(n_real[0])
        assert set(np.asarray(widx[:n]).tolist()) == {1, 4}
        assert set(np.asarray(tile[:n]).tolist()) == {0, 1}
        np.testing.assert_array_equal(offsets, [0, 0, 5, 5, 5, 16, 16])
        assert int(group[n - 1]) == 6      # the zero tail, rows 16..31
