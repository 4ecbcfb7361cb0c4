"""The seam between a family of models and the paged engine
(cake_tpu/models/family.py): every model_type resolves to ONE
description, the description agrees with what the family's programs and
cache really do, the engine refuses exactly what the family's table
names, and the shared modules name no family.
"""

import inspect
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from cake_tpu.models.family import Family, Windows, _CANNOT_MOVE
from cake_tpu.models.llama.config import MODEL_TYPES, LlamaConfig
from cake_tpu.models.llama.model import RopeTables
from cake_tpu.models.llama.paged import PagedKVCache, mixed_token_buckets
from cake_tpu.models.moe.config import (
    BailingHybridConfig, BrumbyConfig, DeepseekV2Config, Dots3NoteConfig, ExaoneMoeConfig,
    GlmMoeDsaConfig, GraniteHybridConfig, KeyeVL2Config, LongcatFlashConfig,
    MoEConfig, NemotronHConfig,
    ZayaConfig,
)
from cake_tpu.obs import steps as obs_steps

ROOT = os.path.join(os.path.dirname(__file__), "..")
TINY = {
    "llama": LlamaConfig.tiny,
    "mistral": partial(LlamaConfig.tiny, chat_template="mistral"),
    "qwen2": partial(LlamaConfig.tiny, attention_bias=True),
    "mixtral": MoEConfig.tiny,
    "olmoe": MoEConfig.tiny_olmoe,
    "glm_moe_dsa": GlmMoeDsaConfig.tiny_glm,
    "dots3_note": Dots3NoteConfig.tiny_dots3,
    "deepseek_v2": DeepseekV2Config.tiny_dsv2,
    "nemotron_h": NemotronHConfig.tiny_nemotron,
    "zaya": ZayaConfig.tiny_zaya,
    "bailing_hybrid": BailingHybridConfig.tiny_ling,
    "exaone_moe": ExaoneMoeConfig.tiny_exaone,
    "granitemoehybrid": GraniteHybridConfig.tiny_granite,
    "KeyeVL2": KeyeVL2Config.tiny_keye,
    "brumby": BrumbyConfig.tiny_brumby,
    "longcat_flash": LongcatFlashConfig.tiny_longcat,
}
# the families whose rows hold more than K/V pages, and the noun of each
NOUNS = {"glm_moe_dsa": "latent row and index key",
         "dots3_note": "latent row and index key",
         "deepseek_v2": "latent row",
         "nemotron_h": "state", "zaya": "tail",
         "bailing_hybrid": "KDA state", "exaone_moe": "K/V ring",
         "granitemoehybrid": "state", "KeyeVL2": "index-key pool",
         "brumby": "state", "longcat_flash": "latent row"}
SLOTS, PAGES, PAGE, WIDTH, SEQ = 4, 16, 4, 8, 64


def tiny_config(model_type: str):
    """The tiny config of a model_type of MODEL_TYPES."""
    return TINY[model_type]()


def init_params(config, dtype=jnp.float32):
    if config.is_moe:
        from cake_tpu.models.moe.params import init_params as init
    else:
        from cake_tpu.models.llama.params import init_params as init
    return init(config, jax.random.PRNGKey(0), dtype)


def test_every_model_type_has_a_tiny_config():
    assert set(TINY) == set(MODEL_TYPES)


@pytest.mark.parametrize("model_type", MODEL_TYPES)
def test_a_family_describes_what_its_programs_and_cache_do(model_type):
    c = tiny_config(model_type)
    f = c.family
    assert isinstance(f, Family) and f is tiny_config(model_type).family
    assert isinstance(f.windows, Windows)
    # its counters: a series for each key, and the vector both step
    # programs return is as long as the keys (a dense model: none)
    assert all(k in obs_steps.COUNTER_SERIES for k in f.counters)
    cache = PagedKVCache.create(c, SLOTS, PAGES, PAGE, SEQ,
                                dtype=jnp.float32, width=WIDTH)
    params = jax.eval_shape(partial(init_params, c))
    rope = RopeTables.create(c, SEQ)
    row = jnp.zeros(SLOTS, jnp.int32)
    live = jnp.ones(SLOTS, bool)
    decode = jax.eval_shape(
        partial(f.decode_step, config=c, attn="fold"), params,
        jnp.zeros((SLOTS, 1), jnp.int32), row, live, cache, rope)
    mixed = jax.eval_shape(
        partial(f.mixed_step, config=c, attn="fold",
                n_tokens=mixed_token_buckets(SLOTS, WIDTH,
                                             f.prefill_rows)[-1]),
        params, jnp.zeros((SLOTS, WIDTH), jnp.int32), row, row + 1, live,
        cache, rope)
    for out in (decode, mixed):
        assert len(out) == (3 if f.counters else 2)
        assert [o.shape for o in out[2:]] == [(len(f.counters),)] * (
            len(out) - 2)
        assert (jax.tree.structure(out[1]) == jax.tree.structure(cache))
    # the recorder takes the engine's keys, and a vector of another
    # length is an error, not a mislabelled series
    flight = obs_steps.StepTelemetry(counters=f.counters)
    if f.counters:
        rec = flight.record("decode", moe=[1.0] * len(f.counters))
        assert tuple(rec.to_dict())[-len(f.counters):] == f.counters
        with pytest.raises(ValueError):
            flight.record("decode", moe=[1.0] * (len(f.counters) + 1))
    # its cache: the pool's bytes and what lies beside it are what the
    # leaves sum to (the page tables apart)
    beside = cache.beside_bytes() if f.beside else 0
    stored = sum(leaf.nbytes for leaf in jax.tree.leaves(cache)
                 if leaf.dtype != jnp.int32)
    assert cache.memory_bytes() + beside == stored
    assert (f.beside is None) == (beside == 0)
    if f.beside is not None:
        what, gauge = f.beside
        assert what and (gauge is None
                         or gauge in obs_steps.BESIDE_POOL_BYTES)
    assert f.impl.startswith("paged-") and f.impl.endswith("-")


def test_a_recorder_refuses_a_key_without_a_series():
    with pytest.raises(ValueError, match="no series"):
        obs_steps.StepTelemetry(counters=("moe_rows", "not_a_counter"))


# -- what a family's rows cannot move yet --------------------------------------


def engine_options(c, params):
    """The seven options of the refusal table, as the engine's
    constructor is handed each."""
    return {
        "--kv-pages": dict(kv_pages=None),
        "topology": dict(step_fns=(print, print)),
        "--spec-draft": dict(spec_draft_params=params, spec_draft_config=c,
                             spec_gamma=2),
        "--kv-dtype": dict(kv_dtype="int8"),
        "--kv-host-pages": dict(kv_host_pages=8),
        "--disagg": dict(disagg="prefill"),
        "--auto-prefix": dict(auto_prefix_system=True),
    }


@pytest.fixture(scope="module")
def models():
    """model_type -> (its tiny config, its seeded parameters), a family
    drawn when a case first asks for it and kept: an xdist worker that
    meets one case draws one family, not all of them."""
    class Drawn(dict):
        def __missing__(self, model_type):
            c = tiny_config(model_type)
            self[model_type] = c, init_params(c)
            return self[model_type]

    return Drawn()


@pytest.mark.parametrize("option", _CANNOT_MOVE)
@pytest.mark.parametrize("model_type", MODEL_TYPES)
def test_an_option_is_refused_where_the_familys_table_names_it(
        model_type, option, models):
    """A family whose table names the option refuses it, by the
    option's name and the family's noun; one that does not says
    nothing."""
    f = tiny_config(model_type).family
    if model_type not in NOUNS:
        assert f.refuses == {} and f.refusal({option: True}) is None
        return
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.serve.engine import InferenceEngine
    assert option in f.refuses and f.refusal({option: False}) is None
    c, params = models[model_type]
    opts = dict(max_slots=SLOTS, max_seq_len=SEQ, kv_pages=PAGES,
                kv_page_size=PAGE, prefill_chunk=WIDTH)
    opts.update(engine_options(c, params)[option])
    with pytest.raises(ValueError) as err:
        InferenceEngine(c, params, ByteTokenizer(c.vocab_size), **opts)
    said = str(err.value)
    assert f"model_type {model_type} " in said and option in said
    assert NOUNS[model_type] in said and "does not serve yet" in said


def readme_table():
    """README.md's table "what the paged engine refuses": {model_type:
    the set of options its row ticks}."""
    with open(os.path.join(ROOT, "README.md")) as fh:
        text = fh.read()
    head = re.search(r"^\| model_type \|(.*)\|$", text, re.M)
    options = [o.strip(" `") for o in head.group(1).split("|")]
    rows = {}
    for line in text[head.end():].splitlines()[2:]:
        if not line.startswith("|"):
            break
        name, *cells = (cell.strip() for cell in line.strip("|").split("|"))
        for model_type in re.findall(r"`(\w+)`", name):
            rows[model_type] = {o for o, cell in zip(options, cells)
                                if cell == "refused"}
    return rows


def test_the_readmes_table_is_the_families_tables():
    rows = readme_table()
    assert set(rows) == set(MODEL_TYPES)
    for model_type, refused in rows.items():
        assert refused == set(tiny_config(model_type).family.refuses), (
            model_type)


# -- the seam stays where it is ------------------------------------------------

NAMES = ("kv_lora_rank", "mamba_layers", "cca_time0", "sliding_layers",
         "nemotron", "zaya", "glm", "dots3", "deepseek", "rope_scaling",
         "n_group", "bailing", "kda", "granite", "longcat", "zero_expert")


@pytest.mark.parametrize("where", ["cake_tpu/serve/engine.py",
                                   "cake_tpu/context.py",
                                   "PagedKVCache.create"])
def test_the_shared_modules_name_no_family(where):
    """The scheduler, the loader and the cache's one entry read a
    family's description: none of them names a family or sniffs an
    attribute of its config."""
    if where.endswith(".py"):
        with open(os.path.join(ROOT, where)) as fh:
            source = fh.read()
    else:
        source = inspect.getsource(PagedKVCache.create)
    found = [name for name in NAMES if name in source]
    assert not found, f"{where} names {found}"


def test_no_counter_layout_is_told_apart_by_its_length():
    assert not hasattr(obs_steps, "counter_layout")
