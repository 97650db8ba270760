"""CLI tests: app/accesskey/channel lifecycle, import/export round trip,
status, train+batchpredict through the console entry point."""

import json
import sys

import pytest

from predictionio_tpu.data.storage import StorageError
from predictionio_tpu.tools import commands
from predictionio_tpu.tools.console import main


@pytest.fixture()
def quiet(monkeypatch):
    """Silence command output."""
    lines = []
    monkeypatch.setattr(commands, "_print", lines.append)
    return lines


class TestAppCommands:
    def test_app_lifecycle(self, memory_storage_env, quiet):
        app, key = commands.app_new("myapp", "desc", out=quiet.append)
        assert app.name == "myapp" and key.key
        with pytest.raises(StorageError, match="already exists"):
            commands.app_new("myapp", out=quiet.append)
        assert [a.name for a in commands.app_list(out=quiet.append)] == ["myapp"]
        info = commands.app_show("myapp", out=quiet.append)
        assert len(info["access_keys"]) == 1
        commands.app_delete("myapp", out=quiet.append)
        assert commands.app_list(out=quiet.append) == []

    def test_channels(self, memory_storage_env, quiet):
        commands.app_new("app1", out=quiet.append)
        ch = commands.channel_new("app1", "live", out=quiet.append)
        assert ch.name == "live"
        with pytest.raises(StorageError, match="already exists"):
            commands.channel_new("app1", "live", out=quiet.append)
        with pytest.raises(StorageError, match="Channel name"):
            commands.channel_new("app1", "bad name!", out=quiet.append)
        commands.channel_delete("app1", "live", out=quiet.append)
        assert commands.app_show("app1", out=quiet.append)["channels"] == []

    def test_data_delete(self, memory_storage_env, quiet):
        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data.storage import Storage

        commands.app_new("app2", out=quiet.append)
        app = Storage.get_meta_data_apps().get_by_name("app2")
        Storage.get_l_events().insert(
            Event(event="x", entity_type="user", entity_id="u"), app.id
        )
        commands.app_data_delete("app2", out=quiet.append)
        assert list(Storage.get_l_events().find(app.id)) == []


class TestAccessKeys:
    def test_lifecycle(self, memory_storage_env, quiet):
        commands.app_new("app3", out=quiet.append)
        key = commands.accesskey_new("app3", ["rate", "buy"], out=quiet.append)
        keys = commands.accesskey_list("app3", out=quiet.append)
        assert any(k.key == key and k.events == ("rate", "buy") for k in keys)
        commands.accesskey_delete(key, out=quiet.append)
        with pytest.raises(StorageError):
            commands.accesskey_delete(key, out=quiet.append)


class TestImportExport:
    def test_round_trip(self, memory_storage_env, quiet, tmp_path):
        commands.app_new("app4", out=quiet.append)
        src = tmp_path / "events.jsonl"
        events = [
            {"event": "rate", "entityType": "user", "entityId": str(u),
             "targetEntityType": "item", "targetEntityId": "i1",
             "properties": {"rating": 4.0},
             "eventTime": "2024-01-01T00:00:00.000Z"}
            for u in range(5)
        ]
        src.write_text("\n".join(json.dumps(e) for e in events) + "\n")
        n = commands.import_events("app4", str(src), out=quiet.append)
        assert n == 5
        dst = tmp_path / "out.jsonl"
        m = commands.export_events("app4", str(dst), out=quiet.append)
        assert m == 5
        exported = [json.loads(l) for l in dst.read_text().splitlines()]
        assert {e["entityId"] for e in exported} == {str(u) for u in range(5)}

    def test_columnar_format_round_trip(self, memory_storage_env, quiet, tmp_path):
        """`pio export --format columnar` -> a segment directory that
        `pio import` re-ingests (the reference's --format parquet role)."""
        from predictionio_tpu.data.store import PEventStore

        commands.app_new("appc", out=quiet.append)
        src = tmp_path / "events.jsonl"
        rows = [
            {"event": "rate", "entityType": "user", "entityId": str(u),
             "targetEntityType": "item", "targetEntityId": f"i{u % 3}",
             "properties": {"rating": float(u % 5 + 1)},
             "eventTime": f"2024-01-01T00:00:{u:02d}.000Z"}
            for u in range(40)
        ]
        src.write_text("\n".join(json.dumps(e) for e in rows) + "\n")
        assert commands.import_events("appc", str(src), out=quiet.append) == 40
        coldir = tmp_path / "colexport"
        assert commands.export_events(
            "appc", str(coldir), format="columnar", out=quiet.append
        ) == 40
        assert any(
            f.startswith("seg-") for _, _, fs in __import__("os").walk(coldir)
            for f in fs
        )
        commands.app_new("appc2", out=quiet.append)
        assert commands.import_events("appc2", str(coldir), out=quiet.append) == 40
        got = sorted(
            (e.entity_id, e.target_entity_id,
             e.properties.get_as("rating", float))
            for e in PEventStore.find(app_name="appc2")
        )
        want = sorted(
            (r["entityId"], r["targetEntityId"], r["properties"]["rating"])
            for r in rows
        )
        assert got == want

    def test_export_unknown_format_rejected(self, memory_storage_env, quiet, tmp_path):
        commands.app_new("appf", out=quiet.append)
        with pytest.raises(ValueError, match="unknown export format"):
            commands.export_events(
                "appf", str(tmp_path / "x"), format="arrow", out=quiet.append
            )

    def test_import_bad_line_reports_location(self, memory_storage_env, quiet, tmp_path):
        commands.app_new("app5", out=quiet.append)
        src = tmp_path / "bad.jsonl"
        src.write_text('{"event": "x", "entityType": "user", "entityId": "u"}\nnot-json\n')
        with pytest.raises(StorageError, match="bad.jsonl:2"):
            commands.import_events("app5", str(src), out=quiet.append)


class TestConsoleEntryPoint:
    def test_version_and_status(self, memory_storage_env, capsys):
        assert main(["version"]) == 0
        assert main(["status"]) == 0
        out = capsys.readouterr().out
        assert "All systems go!" in out
        # N x platform (device_kind), as JAX reports them
        import jax

        assert f"x cpu ({jax.devices()[0].device_kind})" in out

    def test_app_new_via_argv(self, memory_storage_env, capsys):
        assert main(["app", "new", "cliapp"]) == 0
        assert "Access Key" in capsys.readouterr().out
        assert main(["app", "list"]) == 0

    def test_error_exit_code(self, memory_storage_env, capsys):
        assert main(["app", "show", "ghost"]) == 1
        assert "ERROR" in capsys.readouterr().err

    def test_train_and_batchpredict(self, memory_storage_env, capsys, tmp_path):
        variant = {
            "id": "fake-engine", "version": "0.1",
            "engineFactory": "fake_dase:engine0",
            "datasource": {"params": {"base": 10}},
            "algorithms": [{"name": "a0", "params": {"mult": 2}}],
        }
        ej = tmp_path / "engine.json"
        ej.write_text(json.dumps(variant))
        assert main(["train", "--engine-json", str(ej), "--mesh", "none"]) == 0
        assert "Training completed" in capsys.readouterr().out
        queries = tmp_path / "queries.jsonl"
        queries.write_text("1\n2\n")
        results = tmp_path / "results.jsonl"
        assert main([
            "batchpredict", "--engine-json", str(ej),
            "--input", str(queries), "--output", str(results),
        ]) == 0
        lines = [json.loads(l) for l in results.read_text().splitlines()]
        # model = 22 -> prediction = 22 + q
        assert [l["prediction"] for l in lines] == [23, 24]


class TestTemplateCommands:
    def test_template_list(self, quiet):
        templates = commands.template_list(out=quiet.append)
        assert "recommendation" in templates and "twotower" in templates
        assert any("engine_factory" in line for line in quiet)

    def test_template_get_scaffolds_trainable_engine(self, tmp_path, quiet):
        path = commands.template_get(
            "recommendation", str(tmp_path / "eng"), app_name="tplapp",
            out=quiet.append,
        )
        variant = json.load(open(path))
        assert variant["engineFactory"].endswith(":engine_factory")
        assert variant["datasource"]["params"]["appName"] == "tplapp"
        # the scaffold must resolve to a real engine
        from predictionio_tpu.workflow import load_engine_variant

        assert load_engine_variant(variant).build_engine() is not None
        with pytest.raises(ValueError, match="refusing to overwrite"):
            commands.template_get("recommendation", str(tmp_path / "eng"),
                                  out=quiet.append)

    def test_template_get_unknown(self, quiet):
        with pytest.raises(ValueError, match="Unknown template"):
            commands.template_get("nope", "/tmp/x", out=quiet.append)

    def test_every_builtin_scaffold_binds(self, tmp_path):
        """Every scaffolded engine.json must resolve its factory AND bind
        its algorithm names/params — a bad name would only fail at
        train time otherwise."""
        from predictionio_tpu.workflow import load_engine_variant

        for name in commands.BUILTIN_TEMPLATES:
            path = commands.template_get(
                name, str(tmp_path / name), out=lambda _: None
            )
            variant = load_engine_variant(json.load(open(path)))
            engine = variant.build_engine()
            ep = variant.engine_params(engine)  # binds params dataclasses
            assert ep.algorithms, name


class TestRunAndUpgrade:
    def test_run_injects_environment(self, memory_storage_env, tmp_path, capsys):
        script = tmp_path / "probe.py"
        script.write_text(
            "import os, sys\n"
            "import predictionio_tpu  # PYTHONPATH injected\n"
            "sys.exit(0 if os.environ.get('PIO_FS_BASEDIR') else 3)\n"
        )
        rc = main(["run", "--", sys.executable, str(script)])
        assert rc == 0  # probe exits 3 if PIO_FS_BASEDIR was not injected

    def test_run_without_command_errors(self, memory_storage_env, capsys):
        assert main(["run"]) == 1
        assert "needs a command" in capsys.readouterr().err

    def test_upgrade_prints_guidance(self, memory_storage_env, capsys):
        assert main(["upgrade"]) == 0
        assert "pip install -U" in capsys.readouterr().out
