import textwrap

import pytest

from fast_tffm_tpu.config import FmConfig, load_config


def write_cfg(tmp_path, body):
    p = tmp_path / "test.cfg"
    p.write_text(textwrap.dedent(body))
    return str(p)


def test_reference_schema_roundtrip(tmp_path):
    # The reference's sample.cfg shape (SURVEY Appendix A) parses as-is.
    path = write_cfg(tmp_path, """
        [General]
        vocabulary_size = 80000
        vocabulary_block_num = 4
        hash_feature_id = True
        factor_num = 8
        model_file = ./model/fm_model
        log_file = ./log/fm.log

        [Train]
        train_files = data/a.txt, data/b.txt
        epoch_num = 10
        batch_size = 10000
        learning_rate = 0.01
        factor_lambda = 1e-5
        bias_lambda = 1e-5
        init_value_range = 0.01
        loss_type = logistic

        [Predict]
        predict_files = data/test.txt
        score_path = ./score/

        [Cluster]
        ps_hosts = h1:2220,h2:2220
        worker_hosts = h3:2230,h4:2230
    """)
    cfg = load_config(path)
    assert cfg.vocabulary_size == 80000
    assert cfg.hash_feature_id is True
    assert cfg.factor_num == 8
    assert cfg.train_files == ("data/a.txt", "data/b.txt")
    assert cfg.epoch_num == 10
    assert cfg.batch_size == 10000
    assert cfg.factor_lambda == pytest.approx(1e-5)
    assert cfg.worker_hosts == ("h3:2230", "h4:2230")
    assert cfg.row_dim == 9
    assert cfg.pad_id == 80000


def test_appendix_a_cfg_loads_verbatim(tmp_path):
    """SURVEY Appendix A's reconstructed sample.cfg — every key,
    including the [L]-tier ones (weight_files, validation_files,
    save_summaries_steps) — loads without error; no-op reference knobs
    warn instead of raising (round-3 review, missing #3).
    save_summaries_steps is a REAL knob now (utils/summaries.py), so it
    loads silently."""
    path = write_cfg(tmp_path, """
        [General]
        vocabulary_size = 80000000
        vocabulary_block_num = 100
        hash_feature_id = True
        factor_num = 8
        model_file = ./model/fm_model
        log_file = ./log/fm.log

        [Train]
        train_files = data/train_*.txt
        weight_files =
        validation_files =
        epoch_num = 10
        batch_size = 10000
        learning_rate = 0.01
        factor_lambda = 1e-5
        bias_lambda = 1e-5
        init_value_range = 0.01
        loss_type = logistic
        queue_size = 10000
        shuffle_threads = 4
        save_summaries_steps = 100

        [Predict]
        predict_files = data/test_*.txt
        score_path = ./score/

        [Cluster]
        ps_hosts = host1:2220,host2:2220
        worker_hosts = host3:2230,host4:2230
    """)
    with pytest.warns(UserWarning) as rec:
        cfg = load_config(path)
    msgs = [str(w.message) for w in rec]
    assert any("vocabulary_block_num" in m for m in msgs)
    assert cfg.vocabulary_size == 80000000
    assert cfg.save_summaries_steps == 100
    assert cfg.weight_files == () and cfg.validation_files == ()
    assert cfg.ps_hosts == ("host1:2220", "host2:2220")


def test_kernel_pallas_fallback_warns():
    """Explicit kernel=pallas on FFM / order>2 warns and resolves to the
    XLA scorer instead of silently betraying the config (round-3 review,
    weak #2)."""
    from fast_tffm_tpu.models.fm import ModelSpec
    for kwargs in (dict(model_type="ffm", field_num=3),
                   dict(order=3)):
        cfg = FmConfig(kernel="pallas", **kwargs)
        with pytest.warns(UserWarning, match="2nd-order FM"):
            spec = ModelSpec.from_config(cfg)
        assert spec.kernel == "xla"
    # auto never warns — it just resolves.
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ModelSpec.from_config(FmConfig(order=3))


def test_unknown_key_fails_loudly(tmp_path):
    path = write_cfg(tmp_path, """
        [General]
        vocabulary_sizee = 100
    """)
    with pytest.raises(KeyError) as err:
        load_config(path)
    # A true typo must not get a misleading wrong-section hint.
    assert "belongs in" not in str(err.value)


def test_known_key_in_wrong_section_names_its_home(tmp_path):
    """A key placed in the wrong section (the common miss for the
    [General]-homed extension knobs) errors with a pointer to the right
    section; a true typo gets no misleading hint."""
    path = write_cfg(tmp_path, """
        [General]
        vocabulary_size = 100
        [Train]
        lookup = host
    """)
    with pytest.raises(KeyError, match=r"belongs in \[General\]"):
        load_config(path)


def test_missing_file():
    with pytest.raises(FileNotFoundError):
        load_config("/nonexistent/x.cfg")


def test_validation():
    with pytest.raises(ValueError):
        FmConfig(order=1)
    with pytest.raises(ValueError):
        FmConfig(model_type="ffm")          # needs field_num
    with pytest.raises(ValueError):
        FmConfig(model_type="nope")
    with pytest.raises(ValueError):
        FmConfig(loss_type="hinge")
    ffm = FmConfig(model_type="ffm", field_num=5, factor_num=4)
    assert ffm.row_dim == 21


def test_extension_keys(tmp_path):
    path = write_cfg(tmp_path, """
        [General]
        model_type = ffm
        field_num = 3
        factor_num = 2
        order = 2
    """)
    cfg = load_config(path)
    assert cfg.model_type == "ffm"
    assert cfg.row_dim == 7


def test_every_documented_extension_knob_is_reachable(tmp_path):
    """Every knob sample.cfg's header documents must parse from INI —
    a documented-but-unregistered key (dedup was one) strands the
    feature outside the CLI."""
    path = write_cfg(tmp_path, """
        [General]
        vocabulary_size = 100
        model_type = ffm
        field_num = 4
        order = 2
        lookup = device
        dedup = host

        [Train]
        train_files = data/a.txt
        kernel = xla
        dedup = device
        max_features_per_example = 32
        bucket_ladder = 8,32
        uniq_bucket = 128
        validation_max_batches = 5
        shuffle_threads = 3
    """)
    cfg = load_config(path)
    assert cfg.dedup == "device"        # [Train] wins over [General]
    assert cfg.kernel == "xla"
    assert cfg.model_type == "ffm" and cfg.field_num == 4
    assert cfg.lookup == "device"
    assert cfg.bucket_ladder == (8, 32)
    assert cfg.uniq_bucket == 128
    assert cfg.validation_max_batches == 5
    assert cfg.prefetch_depth == 3
