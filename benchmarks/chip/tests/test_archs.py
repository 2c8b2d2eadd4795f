"""Architectures are modules found by name (``archs/<architecture>.py``).

Moving InternLM2's block into its module changed no number: the small
cells give the weights, the first loss and the first gradient that the
commit before the move (76114714caf8b79facf6bc27876f3e8d984011a8) gave.
A new architecture is new files only: a module and a configuration that
names it, loaded from a copy of the benchmark's data, agree with the
program as ``test_reference.py`` requires.  An unknown name is refused.
"""

import dataclasses
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cell
import compare
import harness
from small import small_cell
from test_reference import F32_GAP
from weights import Leaf, layer_rows, plain_shapes, plain_weights

SEED = 20261019
F32_ROUND = 1e-6  # a few float32 roundings: the same program, run again

# The numbers of commit 76114714caf8b79facf6bc27876f3e8d984011a8 at SEED, on
# the CPU: per plain leaf the norm of the seed's weights (one a layer for
# layer leaves), of the first gradient as the program's optimizer got it
# (prog_grad) and as the reference computes it (ref_grad); the first loss
# of each.
PARENT = {
    1: dict(
        weights={
            'attn_norm': [0.0, 0.0],
            'embed': 2.5715106894629396,
            'final_norm': 0.0,
            'head': 2.5356037938287663,
            'mlp_norm': [0.0, 0.0],
            'w_down': [1.7978780429828622, 1.816038035942571],
            'w_gate': [1.811868784838671, 1.8025496180219815],
            'w_up': [1.794092861235211, 1.8191856138479219],
            'wk': [0.9122127288478755, 0.90373700352526],
            'wo': [1.2682935185515367, 1.2652097283497166],
            'wq': [1.3050636495936747, 1.2639342350072902],
            'wv': [0.9109384529938567, 0.9088308805945793],
        },
        prog_grad={
            'attn_norm': [0.004520512651652098, 0.003450949676334858],
            'embed': 0.5677441954612732,
            'final_norm': 0.009846086613833904,
            'head': 0.5609638690948486,
            'mlp_norm': [0.0018391384510323405, 0.0019696406088769436],
            'w_down': [0.07275665551424026, 0.0675392895936966],
            'w_gate': [0.07215937227010727, 0.06732728332281113],
            'w_up': [0.07149772346019745, 0.06698182970285416],
            'wk': [0.0033954589162021875, 0.0031022964976727962],
            'wo': [0.19809915125370026, 0.2066507786512375],
            'wq': [0.0036095604300498962, 0.0030008393805474043],
            'wv': [0.22012853622436523, 0.1857815831899643],
        },
        ref_grad={
            'attn_norm': [0.0045105572789907455, 0.0034482134506106377],
            'embed': 0.567743182182312,
            'final_norm': 0.009854643605649471,
            'head': 0.5609758496284485,
            'mlp_norm': [0.0018366575241088867, 0.0019715437665581703],
            'w_down': [0.0727635845541954, 0.06757309287786484],
            'w_gate': [0.07215946912765503, 0.06733785569667816],
            'w_up': [0.071503646671772, 0.06699313968420029],
            'wk': [0.0033955376129597425, 0.003102295333519578],
            'wo': [0.19807742536067963, 0.20648397505283356],
            'wq': [0.003609240986406803, 0.0030004270374774933],
            'wv': [0.2201441079378128, 0.1858741044998169],
        },
        prog_loss=5.555042266845703,
        ref_loss=5.555030822753906,
    ),
    4: dict(
        weights={
            'attn_norm': [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0],
            'embed': 2.5715106894629396,
            'final_norm': 0.0,
            'head': 2.5356037938287663,
            'mlp_norm': [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0],
            'w_down': [
                1.7978780429828622, 1.816038035942571, 1.821760498382163, 1.8034907017806248,
                1.826270722703567, 1.7850486492173592, 1.7847694274497385, 1.8095292326753867,
            ],
            'w_gate': [
                1.811868784838671, 1.8025496180219815, 1.8065042091997308, 1.777071892080478,
                1.8123480301401746, 1.7842858375044992, 1.8393913954083345, 1.7850245613117157,
            ],
            'w_up': [
                1.794092861235211, 1.8191856138479219, 1.8113842464290049, 1.8240862710180694,
                1.7805281339541998, 1.8049734256859613, 1.7941246945797245, 1.8080272595981008,
            ],
            'wk': [
                0.9122127288478755, 0.90373700352526, 0.8997291564448474, 0.9196674543568554,
                0.9101073118485364, 0.9030920083623659, 0.8620583811332176, 0.9215663082522064,
            ],
            'wo': [
                1.2682935185515367, 1.2652097283497166, 1.2736333800148032, 1.276731746092003,
                1.2599878702754708, 1.280684480612173, 1.2918707412470065, 1.2953830901915695,
            ],
            'wq': [
                1.3050636495936747, 1.2639342350072902, 1.2605888361012514, 1.2772782119704673,
                1.2771378754745895, 1.2763799689056445, 1.2820807599498238, 1.3065819059965917,
            ],
            'wv': [
                0.9109384529938567, 0.9088308805945793, 0.9012861749331822, 0.8775605048150221,
                0.9030428223113528, 0.9023169646006837, 0.8886832077579034, 0.8792787899577206,
            ],
        },
        prog_grad={
            'attn_norm': [
                0.004748874343931675, 0.00322106690146029, 0.004754570312798023,
                0.0034084164071828127, 0.003049608785659075, 0.0032111641485244036,
                0.002805018099024892, 0.0024010816123336554,
            ],
            'embed': 0.35206228494644165,
            'final_norm': 0.006352792028337717,
            'head': 0.3367185592651367,
            'mlp_norm': [
                0.0009425398311577737, 0.0010050104465335608, 0.0009518283768557012,
                0.0007477766484953463, 0.000817669730167836, 0.0006244165706448257,
                0.00047929107677191496, 0.0005972647340968251,
            ],
            'w_down': [
                0.03789426386356354, 0.03335334360599518, 0.029711036011576653,
                0.024679535999894142, 0.023260973393917084, 0.019874127581715584,
                0.019989073276519775, 0.018495772033929825,
            ],
            'w_gate': [
                0.03925632685422897, 0.033615827560424805, 0.02898799069225788,
                0.024993393570184708, 0.02275831066071987, 0.020933330059051514,
                0.019247714430093765, 0.019204266369342804,
            ],
            'w_up': [
                0.037155631929636, 0.033406149595975876, 0.0308703426271677, 0.02593746781349182,
                0.02510335110127926, 0.01904541626572609, 0.019594579935073853,
                0.019063683226704597,
            ],
            'wk': [
                0.0019322707084938884, 0.0013326616026461124, 0.0010853243293240666,
                0.0008237486472353339, 0.0006993187125772238, 0.0005646392819471657,
                0.0004292402882128954, 0.0004143087426200509,
            ],
            'wo': [
                0.22082357108592987, 0.20551535487174988, 0.19671368598937988, 0.16491882503032684,
                0.1776551753282547, 0.15530383586883545, 0.13898149132728577, 0.12919999659061432,
            ],
            'wq': [
                0.0019112330628558993, 0.0013436978915706277, 0.0010757911950349808,
                0.0008704513893462718, 0.0007189902826212347, 0.0006080931634642184,
                0.00037582562072202563, 0.00042461309931240976,
            ],
            'wv': [
                0.238045334815979, 0.19547033309936523, 0.20977044105529785, 0.18127071857452393,
                0.15850646793842316, 0.1490621566772461, 0.15017862617969513, 0.1270347386598587,
            ],
        },
        ref_grad={
            'attn_norm': [
                0.004754272755235434, 0.003215986769646406, 0.0047544208355247974,
                0.003403286449611187, 0.0030447039753198624, 0.0032027862034738064,
                0.0028019538149237633, 0.002400315599516034,
            ],
            'embed': 0.3522283732891083,
            'final_norm': 0.006363502703607082,
            'head': 0.33680015802383423,
            'mlp_norm': [
                0.0009439096320420504, 0.0010054155718535185, 0.0009537308942526579,
                0.0007484666421078146, 0.0008178675197996199, 0.0006260337540879846,
                0.00047904843813739717, 0.0005972230574116111,
            ],
            'w_down': [
                0.03790534660220146, 0.03335418552160263, 0.02971707098186016, 0.024682560935616493,
                0.023258809000253677, 0.01988190785050392, 0.020005611702799797,
                0.018496602773666382,
            ],
            'w_gate': [
                0.0392717644572258, 0.033616211265325546, 0.028987644240260124,
                0.024994390085339546, 0.022767920047044754, 0.02093636244535446,
                0.019252967089414597, 0.019213156774640083,
            ],
            'w_up': [
                0.037168871611356735, 0.03341327980160713, 0.03086295910179615, 0.02594432421028614,
                0.025108294561505318, 0.019052516669034958, 0.019606268033385277,
                0.019066376611590385,
            ],
            'wk': [
                0.0019368019420653582, 0.0013332319213077426, 0.0010849019745364785,
                0.0008231824613176286, 0.0006988962995819747, 0.0005647270590998232,
                0.00042932198266498744, 0.00041394034633412957,
            ],
            'wo': [
                0.22093527019023895, 0.2055620700120926, 0.1966073215007782, 0.16493381559848785,
                0.17753174901008606, 0.1552630513906479, 0.13898448646068573, 0.12917153537273407,
            ],
            'wq': [
                0.0019132402958348393, 0.0013440996408462524, 0.0010762008605524898,
                0.0008697322336956859, 0.000718566938303411, 0.0006078450242057443,
                0.000375568080926314, 0.000423922436311841,
            ],
            'wv': [
                0.2382277548313141, 0.19542992115020752, 0.20980490744113922, 0.18119727075099945,
                0.15837597846984863, 0.14892128109931946, 0.1501009464263916, 0.12704792618751526,
            ],
        },
        prog_loss=5.548156261444092,
        ref_loss=5.548171520233154,
    ),
}


def _norms(leaves, per_layer):
    out = {}
    for name, x in leaves.items():
        x = np.asarray(x, np.float64)
        axes = tuple(range(1, x.ndim)) if name in per_layer else None
        out[name] = np.sqrt(np.sum(x * x, axis=axes))
    return out


def _same(got, want, rel):
    assert sorted(got) == sorted(want)
    for name in want:
        np.testing.assert_allclose(np.asarray(got[name], np.float64), want[name], rtol=rel, err_msg=name)


@pytest.mark.parametrize("pipe", [1, 4])
def test_move_keeps_the_parent_numbers(pipe):
    c = small_cell(pipe)
    c = dataclasses.replace(c, traffic=dict(c.traffic, check_steps=1))
    want = PARENT[pipe]
    b = harness.Bench(c, jax.devices(), SEED)
    weights = plain_weights(c.arch, jnp.asarray(b.key_words), c.config)
    _same(_norms(weights, b.layout.rows), want["weights"], 1e-12)
    state, prog = b.check_steps()
    harness.free(state)
    ref = b.reference()
    assert prog["losses"][0] == pytest.approx(want["prog_loss"], rel=F32_ROUND)
    assert ref["losses"][0] == pytest.approx(want["ref_loss"], rel=F32_ROUND)
    _same(prog["grad_leaf"], want["prog_grad"], F32_ROUND)
    _same(ref["grad_leaf"], want["ref_grad"], F32_ROUND)


# A second name for InternLM2's block, as a new architecture's module would
# be added: a file of its own, found through its configuration.
RE_EXPORT = """
import cell

_base = cell.architecture({"architecture": "internlm2"})
arch_config = _base.arch_config
layer_leaves = _base.layer_leaves
layer = _base.layer
train_flops_per_token = _base.train_flops_per_token
"""


def test_new_architecture_is_new_files(tmp_path):
    here = tmp_path / cell.HERE.relative_to(cell.ROOT)
    shutil.copytree(cell.HERE, here, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(cell.ROOT / "BENCHMARK.json", tmp_path)
    small = small_cell(4)
    (here / "archs" / "dense_copy.py").write_text(RE_EXPORT)
    config = dict(small.config, name="small-copy", architecture="dense_copy", torch_dtype="float32")
    (here / "configs" / "small-copy.json").write_text(json.dumps(config))
    (here / "traffic" / "small_zbv.json").write_text(json.dumps(small.traffic))
    (here / "limits" / "small_copy_zbv.json").write_text(json.dumps(small.limits))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    file = str((here / "configs" / "small-copy.json").relative_to(tmp_path))
    bench["configs"].append(
        {"name": "small-copy", "source": "test", "file": file, "reduced": [], "why": "test"}
    )
    bench["workloads"].append(
        {"name": "small_copy_zbv", "config": "small-copy", "traffic": "small_zbv", "chips": 4,
         "why": "test"}
    )
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    c = cell.load("small_copy_zbv", tmp_path)
    assert c.arch.__file__ == str(here / "archs" / "dense_copy.py")
    b = harness.Bench(c, jax.devices(), 5)
    state, prog = b.check_steps()
    harness.free(state)
    nums = compare.numbers(prog, b.reference())
    assert all(v < F32_GAP for v, _ in nums.values()), nums


@pytest.mark.parametrize("config", [{"name": "x"}, {"name": "x", "architecture": "no_such_block"}])
def test_unknown_architecture_is_refused(config):
    with pytest.raises(ValueError, match="archs") as err:
        cell.architecture(config)
    assert "internlm2" in str(err.value)  # the modules there are listed


class _LeadingDense:
    """One dense layer, then layers of another kind: leaves that only some
    layers have are stacked over those layers alone."""

    @staticmethod
    def layer_leaves(config, li):
        if li == 0:
            second = {"w_up": Leaf("mlp", "wu", (4, 8), 0.02)}
        else:
            second = {"experts_up": Leaf("moe", "wu", (2, 4, 3), 0.02)}
        return {"attn_norm": Leaf("attn", "ln", (4,)), "wq": Leaf("attn", "wq", (4, 4), 0.02), **second}


def test_leaves_of_some_layers_stack_over_those():
    config = dict(
        num_hidden_layers=3, hidden_size=4, vocab_size=16, initializer_range=0.02, torch_dtype="float32"
    )
    arch = _LeadingDense()
    assert layer_rows(arch, config) == {
        "attn_norm": {0: 0, 1: 1, 2: 2},
        "wq": {0: 0, 1: 1, 2: 2},
        "w_up": {0: 0},
        "experts_up": {1: 0, 2: 1},
    }
    assert plain_shapes(arch, config) == {
        "embed": (16, 4), "head": (4, 16), "final_norm": (4,),
        "attn_norm": (3, 4), "wq": (3, 4, 4), "w_up": (1, 4, 8), "experts_up": (2, 2, 4, 3),
    }
    w = plain_weights(arch, jnp.zeros(2, jnp.uint32), config)
    assert not np.any(np.asarray(w["attn_norm"])) and not np.any(np.asarray(w["final_norm"]))
    assert np.asarray(w["experts_up"]).std() > 0


def test_attend_takes_a_value_head_dim_of_its_own():
    """Causal attention with qk heads of 6 and v heads of 4, as MLA has
    them, against softmax over the masked scores written out."""
    from reference import Reference

    small = small_cell(1)
    ref = Reference(small.arch, small.config, small.traffic["optimizer"])
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (1, 8, 2, 6))
    k = jax.random.normal(kk, (1, 8, 2, 6))
    v = jax.random.normal(kv, (1, 8, 2, 4))
    with jax.default_matmul_precision("highest"):
        got = ref.attend(q, k, v)
        z = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(6)
        z = jnp.where(jnp.tril(jnp.ones((8, 8), bool)), z, -jnp.inf)
        want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(z, axis=-1), v)
    assert got.shape == (1, 8, 2, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
