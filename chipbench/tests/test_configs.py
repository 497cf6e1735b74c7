"""Each configuration file states the published widths, and the program's
configuration agrees with every width the file states."""
import pytest

from chipbench import cells as C

PUBLISHED = {
    "phi3-mini-3.8b": dict(hidden_size=3072, intermediate_size=8192,
                           num_attention_heads=32, num_key_value_heads=32,
                           head_dim=96, num_hidden_layers=32,
                           vocab_size=32064, tie_word_embeddings=False),
    "granite-3-8b-d20": dict(hidden_size=4096, intermediate_size=12800,
                             num_attention_heads=32, num_key_value_heads=8,
                             head_dim=128, num_hidden_layers=20,
                             vocab_size=49155, tie_word_embeddings=True),
}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_widths_are_published(name):
    cfg = C.load_config(name)
    for key, value in PUBLISHED[name].items():
        assert cfg[key] == value, key
    prog = C.program_config(cfg)
    assert prog.d_model == cfg["hidden_size"]
    assert prog.n_layers == cfg["num_hidden_layers"]
    assert prog.resolved_head_dim == cfg["head_dim"]


def test_granite_cut_is_listed():
    cfg = C.load_config("granite-3-8b-d20")
    assert cfg["published"]["num_hidden_layers"] == 40
    assert set(cfg["published"]) == set(cfg["reduced"])


def test_program_config_refuses_a_wrong_width():
    cfg = dict(C.load_config("phi3-mini-3.8b"), hidden_size=4096)
    with pytest.raises(C.CellError):
        C.program_config(cfg)
