"""Config validation pass + TOML loading (ref Config::load rejecting
unknown fields and validateConfig's quorum-safety rules)."""
import pytest

from stellar_core_tpu.crypto import SecretKey, blake2, sha256
from stellar_core_tpu.crypto.strkey import (
    encode_ed25519_public_key, encode_ed25519_seed,
)
from stellar_core_tpu.main.config import (
    Config, ConfigError, test_config as make_config,
)


def _vkeys(n):
    return [SecretKey(sha256(b"cfg-%d" % i)).public_key().raw
            for i in range(n)]


def test_valid_config_passes():
    make_config().validate()


def test_bad_ports_rejected():
    with pytest.raises(ConfigError, match="PEER_PORT"):
        make_config(PEER_PORT=70000).validate()
    with pytest.raises(ConfigError, match="must differ"):
        make_config(PEER_PORT=11625, HTTP_PORT=11625).validate()
    # 0 / None are listener-disable sentinels, not errors
    make_config(PEER_PORT=0, HTTP_PORT=None).validate()


def test_bad_invariant_regex_rejected():
    with pytest.raises(ConfigError, match="INVARIANT_CHECKS"):
        make_config(INVARIANT_CHECKS=["("]).validate()


def test_unsafe_quorum_threshold_rejected():
    # 4 validators tolerating f=1 need threshold >= 3
    qs = {"threshold": 2, "validators": _vkeys(4)}
    with pytest.raises(ConfigError, match="unsafe"):
        make_config(QUORUM_SET=qs, UNSAFE_QUORUM=False).validate()
    make_config(QUORUM_SET=qs).validate()  # test default is UNSAFE_QUORUM
    make_config(QUORUM_SET={"threshold": 3, "validators": _vkeys(4)},
                UNSAFE_QUORUM=False).validate()


def test_failure_safety_override():
    # explicit FAILURE_SAFETY=0 makes threshold n required
    qs = {"threshold": 3, "validators": _vkeys(4)}
    with pytest.raises(ConfigError, match="unsafe"):
        make_config(QUORUM_SET=qs, FAILURE_SAFETY=0,
                    UNSAFE_QUORUM=False).validate()


def test_duplicate_validator_rejected():
    k = _vkeys(1)[0]
    with pytest.raises(ConfigError, match="duplicate"):
        make_config(QUORUM_SET={"threshold": 2,
                                "validators": [k, k]}).validate()


def test_validator_without_quorum_set_rejected():
    with pytest.raises(ConfigError, match="QUORUM_SET"):
        Config(NODE_IS_VALIDATOR=True, RUN_STANDALONE=False,
               NODE_SEED=sha256(b"x")).validate()


def test_toml_unknown_key_rejected(tmp_path):
    p = tmp_path / "bad.toml"
    p.write_text('no_such_knob = 1\n')
    with pytest.raises(ConfigError, match="unknown configuration key"):
        Config.from_toml(str(p))


def test_toml_roundtrip_validates(tmp_path):
    seed = sha256(b"toml-node")
    pub = SecretKey(seed).public_key().raw
    p = tmp_path / "node.toml"
    p.write_text(f"""
network_passphrase = "toml test net"
node_seed = "{encode_ed25519_seed(seed)}"
peer_port = 17001
http_port = 17002
max_slots_to_remember = 24
catchup_complete = true
preferred_peers = ["127.0.0.1:17003"]

[quorum_set]
threshold = 1
validators = ["{encode_ed25519_public_key(pub)}"]
""")
    cfg = Config.from_toml(str(p))
    assert cfg.MAX_SLOTS_TO_REMEMBER == 24
    assert cfg.CATCHUP_COMPLETE is True
    assert cfg.PREFERRED_PEERS == ["127.0.0.1:17003"]


def test_blake2_vectors():
    # RFC 7693 appendix A reduced to digest_size=32 is not published;
    # pin against hashlib's own blake2b-256 and check basic properties
    assert len(blake2(b"")) == 32
    assert blake2(b"abc") != blake2(b"abd")
    assert blake2(b"abc") == blake2(b"abc")
    # known blake2b-256("abc") test vector (public, widely published)
    assert blake2(b"abc").hex() == (
        "bddd813c634239723171ef3fee98579b94964e3bb1cb3e427262c8c068d52319")


class TestAutoBackends:
    def test_auto_resolves_on_application_construction(self, monkeypatch):
        """CRYPTO_BACKEND/SCP_TALLY_BACKEND default to "auto" and resolve
        from the process's own JAX backend at Application construction:
        the device tiers on a TPU, the host tiers otherwise."""
        import jax

        from stellar_core_tpu.main import Application, test_config
        from stellar_core_tpu.main.config import Config
        from stellar_core_tpu.utils.clock import ClockMode, VirtualClock

        assert Config().CRYPTO_BACKEND == "auto"
        assert Config().SCP_TALLY_BACKEND == "auto"

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cfg = test_config(CRYPTO_BACKEND="auto", SCP_TALLY_BACKEND="auto")
        app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
        assert app.config.CRYPTO_BACKEND == "tpu"
        assert app.config.SCP_TALLY_BACKEND == "tensor"

        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        cfg2 = test_config(CRYPTO_BACKEND="auto", SCP_TALLY_BACKEND="auto")
        app2 = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg2)
        assert app2.config.CRYPTO_BACKEND == "cpu"
        assert app2.config.SCP_TALLY_BACKEND == "host"

    def test_explicit_override_respected(self, monkeypatch):
        import jax

        from stellar_core_tpu.main import Application, test_config
        from stellar_core_tpu.utils.clock import ClockMode, VirtualClock

        # an explicit setting wins over what "auto" would resolve to
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cfg = test_config()  # pins cpu/host
        app = Application(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
        assert app.config.CRYPTO_BACKEND == "cpu"
        assert app.config.SCP_TALLY_BACKEND == "host"


def test_compilation_cache_placement(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the cache is
    the one fixed directory inside the checkout."""
    import os

    import jax

    from stellar_core_tpu.utils import device

    before = jax.config.jax_compilation_cache_dir
    try:
        env_dir = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert device.enable_compilation_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir == env_dir

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        got = device.enable_compilation_cache()
        assert got == device.DEFAULT_CACHE_DIR \
            == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
