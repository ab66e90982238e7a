"""``analysis.hlo.op_scopes``: from an optimized executable's ``op_name``
metadata to the ``jax.named_scope`` each instruction ran under."""

import jax
import jax.numpy as jnp

from sheeprl_tpu.analysis.hlo import op_scopes

REGIONS = ("wm.encoder", "wm.dynamics", "wm.heads", "wm.optim")


def _compiled_text():
    def f(w, x):
        def loss(w):
            with jax.named_scope("wm.encoder"):
                e = jnp.tanh(x @ w)
            with jax.named_scope("wm.dynamics"):

                def step(h, e_t):
                    with jax.named_scope("kernel.gru_gates"):
                        h = jnp.tanh(h @ w + e_t)
                    return h, h

                _, hs = jax.lax.scan(step, jnp.zeros_like(e[0]), e)
            with jax.named_scope("wm.heads"):
                return jnp.sum(hs**2)

        value, grad = jax.value_and_grad(loss)(w)
        with jax.named_scope("wm.optim"):
            w = w - 0.1 * grad
        return w, value

    return jax.jit(f).lower(jnp.ones((8, 8)), jnp.ones((5, 4, 8))).compile().as_text()


def test_forward_backward_and_kernel_scopes_of_a_scan_under_grad():
    table = op_scopes(_compiled_text(), REGIONS)
    kinds = {(v["outer"], v["scope"], v["backward"]) for v in table.values()}
    # forward and backward of one region are told apart by transpose(jvp(...)) alone
    assert ("wm.dynamics", "wm.dynamics", False) in kinds and ("wm.dynamics", "wm.dynamics", True) in kinds
    assert ("wm.encoder", "wm.encoder", False) in kinds and ("wm.encoder", "wm.encoder", True) in kinds
    # a kernel's time counts in its region (outer) and under its own name (scope), both ways
    assert ("wm.dynamics", "kernel.gru_gates", False) in kinds and ("wm.dynamics", "kernel.gru_gates", True) in kinds
    assert ("wm.optim", "wm.optim", False) in kinds and ("wm.optim", "wm.optim", True) not in kinds
    # parameters and plumbing carry no scope; nothing is guessed for them
    assert (None, None, False) in kinds
    assert all(v["outer"] in REGIONS + (None,) for v in table.values())
    whiles = [k for k, v in table.items() if k.startswith("while") and v["outer"] == "wm.dynamics"]
    assert {table[k]["backward"] for k in whiles} == {False, True}


def test_joined_missing_and_unknown_op_names():
    text = "\n".join(
        [
            "HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}",
            "%fused_computation (p: f32[8]) -> f32[8] {",
            '  %p = f32[8]{0} parameter(0), metadata={op_name="w"}',
            '  ROOT %add.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(f)/jit(main)/wm.heads/add" source_file="a.py" source_line=3}',
            "}",
            'ENTRY %main.5 (w: f32[8]) -> f32[8] {',
            '  %fusion.7 = f32[8]{0} fusion(%w), kind=kLoop, calls=%fused_computation, '
            'metadata={op_name="jit(f)/transpose(jvp(wm.dynamics))/while/body/mul;jit(f)/jvp(wm.encoder)/tanh"}',
            "  %copy.3 = f32[8]{0:T(8)} copy(%fusion.7)",
            '  %gru_gates.20 = f32[8]{0} custom-call(%copy.3), custom_call_target="tpu_custom_call", '
            'metadata={op_name="jit(f)/jvp(behaviour.imagination)/while/body/closed_call/kernel.gru_gates/cond/'
            'jit(f)/jvp(behaviour.imagination)/kernel.gru_gates/cond/branch_0_fun/gru_gates/pallas_call"}',
            '  ROOT %other.1 = f32[8]{0} negate(%gru_gates.20), metadata={op_name="jit(f)/some.other/neg"}',
            "}",
        ]
    )
    table = op_scopes(text, REGIONS + ("behaviour.imagination",))
    # the first of a ;-joined list decides
    assert table["fusion.7"] == {"scope": "wm.dynamics", "outer": "wm.dynamics", "backward": True}
    # no metadata: in the table, attributed to nothing
    assert table["copy.3"] == {"scope": None, "outer": None, "backward": False}
    assert table["gru_gates.20"] == {"scope": "kernel.gru_gates", "outer": "behaviour.imagination", "backward": False}
    # a dotted name that is no region is not taken for one
    assert table["other.1"] == {"scope": None, "outer": None, "backward": False}
    assert table["add.1"]["outer"] == "wm.heads" and table["p"]["outer"] is None
    assert "main.5" not in table and "fused_computation" not in table  # computations are not instructions


def test_kernel_prefix_and_regions_are_the_callers():
    table = op_scopes(_compiled_text(), ("wm.dynamics",), kernel_prefix="nothing.")
    assert {v["scope"] for v in table.values()} == {None, "wm.dynamics"}


def test_while_readers_tell_what_a_loop_holds_from_what_runs_outside_it():
    """``while_carried_shapes`` and ``while_body_shapes``: a matrix the scan's
    step applies rides the loop's carry and is named in its body; one applied
    to the stacked outputs, after the loop, is in neither."""
    from sheeprl_tpu.analysis.hlo import while_body_shapes, while_carried_shapes

    def f(w_in, w_out, x):
        _, hs = jax.lax.scan(lambda h, x_t: (jnp.tanh(h @ w_in + x_t),) * 2, jnp.zeros_like(x[0]), x)
        return jnp.tanh(hs @ w_out)

    text = jax.jit(f).lower(jnp.ones((8, 8)), jnp.ones((8, 6)), jnp.ones((5, 4, 8))).compile().as_text()
    (carried,), (body,) = while_carried_shapes(text), while_body_shapes(text)
    inside, outside = ("f32", (8, 8)), ("f32", (8, 6))
    assert inside in carried and inside in body
    assert outside not in carried and outside not in body
    assert set(carried) <= body, "a loop's carry is its body's parameter"


def test_an_instruction_printed_over_several_lines_keeps_its_op_name():
    """A Pallas call that carries `kernel_metadata` is printed over three lines, its `metadata=` on the last."""
    from sheeprl_tpu.analysis.hlo import op_scopes

    text = "\n".join([
        '  %splash_mqa_fwd.1 = (f32[4,512,128]{2,1,0}, bf16[4,7,8192,128]{3,2,1,0}) custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={',
        '"xprof_metadata":"{\\"block_q\\": 512}"',
        '}}, metadata={op_name="jit(block)/while/body/transpose(jvp())/lm.attn_window/kernel.window_attention/'
        'vmap(jit(_splash_attention))/pallas_call" stack_frame_id=5}, backend_config={"x":1}',
        '  %pallas_call.2 = bf16[4,7,8192,128]{3,2,1,0} get-tuple-element(%splash_mqa_fwd.1), index=1',
        '  %gmm.3 = f32[8,8]{1,0} custom-call(%c), frontend_attributes={kernel_metadata={}}, '
        'metadata={op_name="jit(block)/rollout.decode/lm.moe/kernel.moe_grouped_ffn/gmm"}',
    ])
    table = op_scopes(text, regions=("lm.attn_window", "lm.moe", "rollout.decode"))
    assert table["splash_mqa_fwd.1"] == {"scope": "kernel.window_attention", "outer": "lm.attn_window", "backward": True}
    assert table["pallas_call.2"] == {"scope": None, "outer": None, "backward": False}  # no metadata of its own
    assert table["gmm.3"] == {"scope": "kernel.moe_grouped_ffn", "outer": "rollout.decode", "backward": False}
