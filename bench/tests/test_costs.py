"""The cost models count the parameters the served model holds."""
import jax
import pytest

from bench import harness

CELLS = ["yi-9b-16l.chat-open", "rwkv6-1.6b.chat-open"]


def _program_shapes(cell):
    from repro.models.registry import build_model
    model = build_model(harness.model_config(cell.cfg))
    return model, jax.eval_shape(model.init, jax.random.PRNGKey(0))


@pytest.mark.parametrize("name", CELLS)
def test_param_bytes_match_the_program(name):
    cell = harness.load_cell(name)
    _, shapes = _program_shapes(cell)
    leaves = jax.tree.leaves(shapes)
    assert cell.costs.param_count(cell.cfg) == sum(a.size for a in leaves)
    assert cell.costs.param_bytes(cell.cfg) == sum(
        a.size * a.dtype.itemsize for a in leaves)


@pytest.mark.parametrize("name,gb", [(CELLS[0], 6.06), (CELLS[1], 2.93)])
def test_param_bytes_at_published_widths(name, gb):
    cell = harness.load_cell(name)
    assert round(cell.costs.param_bytes(cell.cfg) / 1e9, 2) == gb


@pytest.mark.parametrize("name,slack", [(CELLS[0], 0.0), (CELLS[1], 0.02)])
def test_param_count_against_model_config(name, slack):
    """``ModelConfig.param_count`` leaves out yi's final norm and stands
    in 2.5 d^2 for rwkv6's low-rank mixes (which are 0.9 M a layer)."""
    cell = harness.load_cell(name)
    model, _ = _program_shapes(cell)
    ours = cell.costs.param_count(cell.cfg)
    theirs = model.cfg.param_count()
    assert abs(ours - theirs) <= max(cell.cfg["d_model"], slack * theirs)


@pytest.mark.parametrize("name", CELLS)
def test_weights_have_the_program_layout(name):
    cell = harness.load_cell(name)
    _, shapes = _program_shapes(cell)
    ours = jax.eval_shape(
        lambda k: cell.ref.make_weights(cell.cfg, k), jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), ours) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), shapes)


@pytest.mark.parametrize("name", CELLS)
def test_stages_scale_with_rows(name):
    """Operations grow in proportion to rows; weight bytes do not."""
    cell = harness.load_cell(name)
    one = cell.costs.stages(cell.cfg, 1, cell.prompt_len, cell.steps)
    eight = cell.costs.stages(cell.cfg, 8, cell.prompt_len, cell.steps)
    assert len(one) == cell.steps + 1
    for (f1, b1), (f8, b8) in zip(one, eight):
        assert f8 == pytest.approx(8 * f1)
        assert b1 < b8 < 8 * b1
