"""Each plain reference against the program at ``TransformerConfig.tiny``
widths on the CPU, and the tolerance the chip check uses against three
wrong architectures."""
import jax
import pytest

from bench_paths import tiny_config

from benchmark import harness
from benchmark.engines.trainer import Engine
from benchmark.generators import zipf_lm
from benchmark.models import transformer

TRAFFIC = dict(generator='zipf_lm', seq=32, global_batch=4,
               zipf_exponent=1.1)


@pytest.fixture(scope='module', params=[True, False],
                ids=['causal', 'masked'])
def case(request):
    """The program in bf16 (as on the chip) against the f32 reference."""
    config = tiny_config(request.param, dtype='bfloat16')
    engine = Engine(transformer.build(config), {'dp': 1}, jax.devices()[:1])
    state = engine.init(0)
    probe = next(zipf_lm.batches(TRAFFIC, config, 0, batch=4, stream=1))
    got = engine.loss_and_grad_norm(state, probe)
    return config, transformer.to_reference_params(state.params), probe, got


def agrees(got, want):
    return harness.close(got[0], want[0], harness.LOSS_RTOL) and \
        harness.close(got[1], want[1], harness.GRAD_NORM_RTOL)


def test_reference_agrees_with_the_program(case):
    config, ref_params, probe, got = case
    want = transformer.reference_loss_and_grad_norm(config, ref_params,
                                                    probe)
    assert agrees(got, want), (got, want)


@pytest.mark.parametrize('broken', ['attention_scale', 'mask', 'final_ln'])
def test_a_wrong_architecture_is_outside_the_tolerance(case, broken):
    config, ref_params, probe, got = case
    wrong = transformer.reference_loss_and_grad_norm(
        config, ref_params, probe, **{broken: False})
    # the masked-LM configuration has no causal mask to remove
    still_right = broken == 'mask' and not config['causal']
    assert agrees(got, wrong) == still_right, (got, wrong)


def test_name_map_covers_every_parameter(case):
    config, ref_params, _, _ = case
    engine = Engine(transformer.build(config), {'dp': 1}, jax.devices()[:1])
    params = engine.init(0).params
    assert len(jax.tree.leaves(ref_params)) == len(jax.tree.leaves(params))
