"""Config provider regression tests (falsy defaults, env/flag precedence,
the engine modes a config may name)."""

import json

import pytest

from keto_tpu.driver.config import Config
from keto_tpu.utils.errors import ErrMalformedInput


class TestConfigDefaults:
    def test_explicit_falsy_defaults_are_honored(self):
        c = Config(values={}, env={})
        # a caller-provided falsy default must not fall through to DEFAULTS
        assert c.get("engine.batch_window_us", default=0) == 0
        assert c.get("serve.read.port", default=0) == 0
        assert c.get("log.level", default="") == ""
        assert c.get("namespaces", default=False) is False

    def test_missing_key_without_default_uses_defaults_table(self):
        c = Config(values={}, env={})
        assert c.get("serve.read.port") == 4466
        assert c.get("engine.mode") == "closure"
        assert c.get("no.such.key") is None

    def test_data_value_wins_over_default(self):
        c = Config(values={"serve": {"read": {"port": 1234}}}, env={})
        assert c.get("serve.read.port", default=0) == 1234

    def test_env_override_wins(self):
        c = Config(values={}, env={"KETO_SERVE_READ_PORT": "9999"})
        assert c.get("serve.read.port", default=0) == 9999

    def test_flag_override_wins_over_env(self):
        c = Config(
            values={},
            env={"KETO_SERVE_READ_PORT": "9999"},
            flag_overrides={"serve.read.port": 1111},
        )
        assert c.get("serve.read.port") == 1111


class TestShardingConfig:
    def test_sharding_defaults(self):
        c = Config(values={}, env={})
        assert c.get("engine.sharding.enabled") is False
        assert c.get("engine.sharding.data") == 1
        assert c.get("engine.sharding.edge") == 0
        assert c.get("engine.sharding.edge_chunk") == 0
        assert c.get("engine.sharding.escalation_budget") == 0.05

    def test_sharding_values_round_trip(self):
        c = Config(
            values={
                "engine": {
                    "sharding": {
                        "enabled": True,
                        "data": 2,
                        "edge": 4,
                        "edge_chunk": 1 << 20,
                        "escalation_budget": 0.01,
                    }
                }
            },
            env={},
        )
        assert c.get("engine.sharding.enabled") is True
        assert c.get("engine.sharding.data") == 2
        assert c.get("engine.sharding.edge") == 4
        assert c.get("engine.sharding.edge_chunk") == 1 << 20
        assert c.get("engine.sharding.escalation_budget") == 0.01

    def test_sharding_env_override(self):
        c = Config(
            values={}, env={"KETO_ENGINE_SHARDING_ENABLED": "true"}
        )
        assert c.get("engine.sharding.enabled") in (True, "true")

    def test_sharding_keys_in_exported_schema(self):
        from keto_tpu.driver.config import CONFIG_SCHEMA

        props = CONFIG_SCHEMA["properties"]["engine"]["properties"]
        sharding = props["sharding"]["properties"]
        assert set(sharding) == {
            "enabled", "data", "edge", "edge_chunk", "escalation_budget"
        }
        # misspelled keys must be rejected, same as every engine block
        assert props["sharding"]["additionalProperties"] is False

    def test_sharding_keys_validate(self):
        import jsonschema
        import pytest
        from keto_tpu.driver.config import CONFIG_SCHEMA

        jsonschema.validate(
            {"engine": {"sharding": {"enabled": True, "data": 2}}},
            CONFIG_SCHEMA,
        )
        with pytest.raises(jsonschema.ValidationError):
            jsonschema.validate(
                {"engine": {"sharding": {"escalation_budget": 2.0}}},
                CONFIG_SCHEMA,
            )


class TestEngineModes:
    @pytest.mark.parametrize("mode", ["host", "auto", "closure"])
    def test_served_modes_load(self, tmp_path, mode):
        path = tmp_path / "keto.yml"
        path.write_text(f"engine:\n  mode: {mode}\n")
        assert Config(config_file=str(path), env={}).engine_mode() == mode

    @pytest.mark.parametrize(
        "engine",
        [
            {"mode": "dense"},
            {"mode": "device"},
            {"mode": "scatter"},
            {"mode": "packed"},
            {"mode": "sharded"},
            {"mesh": {"data": 1, "edge": 0}},
        ],
    )
    def test_removed_engine_settings_refused_at_load(self, tmp_path, engine):
        """A config file naming a deleted engine mode or one of its
        options fails to load with the schema error."""
        path = tmp_path / "keto.json"
        path.write_text(json.dumps({"engine": engine}))
        with pytest.raises(ErrMalformedInput, match="invalid configuration"):
            Config(config_file=str(path), env={})
