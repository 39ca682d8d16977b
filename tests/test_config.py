"""Config system round-trip tests (the Tang serialize/ship/re-inject
analogue; ref: AvroConfigurationSerializer usage in ETDolphinLauncher)."""
import pytest

from harmony_tpu.config import (
    ConfigBase,
    JobConfig,
    TableConfig,
    TrainerParams,
    resolve_symbol,
    symbol_name,
)


def test_table_config_roundtrip():
    tc = TableConfig(
        table_id="model",
        capacity=7840,
        value_shape=(10,),
        num_blocks=64,
        is_ordered=True,
        update_fn="add",
    )
    back = ConfigBase.from_json(tc.to_json())
    assert back == tc
    assert back.value_shape == (10,)


def test_job_config_nested_roundtrip():
    jc = JobConfig(
        job_id="mlr-0",
        app_type="dolphin",
        trainer="harmony_tpu.apps.mlr:MLRTrainer",
        tables=[
            TableConfig(table_id="model", capacity=100, value_shape=(4,), num_blocks=8),
            TableConfig(table_id="input", capacity=1000, num_blocks=16, is_ordered=False),
        ],
        params=TrainerParams(num_epochs=3, num_mini_batches=5, clock_slack=2),
    )
    back = ConfigBase.from_json(jc.to_json())
    assert back == jc
    assert back.tables[1].is_ordered is False
    assert back.params.clock_slack == 2


def test_symbol_roundtrip():
    import harmony_tpu.table.update as mod

    path = symbol_name(mod.get_update_fn)
    assert resolve_symbol(path) is mod.get_update_fn


# -- the fields PR 27 retired with the unfused and async step modes ---------

#: what every ``TrainerParams.to_dict()`` wrote for them until PR 27
PARENT_DEFAULTS = {"fused_step": True, "async_step": False,
                   "staleness_bound": 0}


def _as_the_parent_wrote(job, **changed):
    """``job.to_dict()`` as the parent commit serialized it: every field,
    defaults included — so the three retired ones are there."""
    conf = job.to_dict()
    conf["params"].update({**PARENT_DEFAULTS, **changed})
    return conf


def _mlr_job(job_id):
    return JobConfig(
        job_id=job_id, app_type="dolphin",
        trainer="harmony_tpu.apps.mlr:MLRTrainer",
        params=TrainerParams(
            num_epochs=1, num_mini_batches=2,
            app_params={"num_classes": 4, "num_features": 16,
                        "features_per_partition": 4}),
        num_workers=1,
        user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
              "data_args": {"n": 64, "num_features": 16, "num_classes": 4,
                            "seed": 7}},
    )


@pytest.mark.parametrize("field,value,decodes", [
    ("fused_step", True, True),
    ("async_step", False, True),
    ("staleness_bound", 0, True),
    ("fused_step", False, False),
    ("async_step", True, False),
    ("staleness_bound", 2, False),
])
def test_retired_field_decodes_only_at_its_old_default(field, value, decodes):
    assert TrainerParams.RETIRED_FIELDS[field][0] == PARENT_DEFAULTS[field]
    wire = {**TrainerParams(num_epochs=3).to_dict(), field: value}
    if decodes:
        back = ConfigBase.from_dict(wire)
        assert back == TrainerParams(num_epochs=3)
        assert not hasattr(back, field) and field not in back.to_dict()
    else:
        with pytest.raises(ValueError, match=rf"{field}.*PR 27"):
            ConfigBase.from_dict(wire)


def _replay_from_ha_log(confs, tmp_path):
    """A takeover's re-arm of the submissions a leader logged."""
    from harmony_tpu.jobserver.ha import HAController, ReplayState

    state = ReplayState.from_entries([
        {"seq": i + 1, "epoch": 1, "kind": "submission",
         "job": conf["job_id"], "config": conf}
        for i, conf in enumerate(confs)])

    class Server:
        _chkp_root = str(tmp_path)
        submitted = []

        def submit(self, cfg):
            self.submitted.append(cfg)

    server = Server()
    rearmed = HAController._rearm(
        HAController.__new__(HAController), server, state)
    assert rearmed == [c.job_id for c in server.submitted]
    return rearmed, None  # a failed re-arm is logged, not replied to


def _submit_over_tcp(confs, tmp_path):
    """An older client's SUBMIT: the conf as a plain dict on the wire."""
    import jax

    from harmony_tpu.jobserver.client import CommandSender
    from harmony_tpu.jobserver.server import JobServer
    from harmony_tpu.parallel.mesh import DevicePool

    server = JobServer(1, device_pool=DevicePool(jax.devices()[:1]))
    server.start()
    try:
        sender = CommandSender(server.serve_tcp())
        replies = {c["job_id"]: sender._roundtrip(
            {"command": "SUBMIT", "conf": c}) for c in confs}
        accepted = [j for j, r in replies.items() if r["ok"]]
        for job in accepted:  # an accepted job runs to its end
            result = sender.wait_result(job, timeout=120)
            assert all(w["losses"] for w in result["workers"].values())
        return accepted, replies
    finally:
        server.shutdown(timeout=60)


@pytest.mark.parametrize("route", [_replay_from_ha_log, _submit_over_tcp],
                         ids=["ha-log", "tcp-submit"])
def test_conf_the_parent_wrote_still_starts(route, tmp_path):
    """A submission serialized before PR 27 carries all three retired
    fields: at their defaults it replays from the HA log and submits over
    TCP; one that asked for a deleted mode is refused, the others go on."""
    confs = [_as_the_parent_wrote(_mlr_job("old-ok")),
             _as_the_parent_wrote(_mlr_job("old-unfused"), fused_step=False),
             _as_the_parent_wrote(_mlr_job("old-async"), async_step=True,
                                  staleness_bound=1)]
    started, replies = route(confs, tmp_path)
    assert started == ["old-ok"]
    if replies is not None:
        assert "fused_step" in replies["old-unfused"]["error"]
        assert "async_step" in replies["old-async"]["error"]
        assert all("PR 27" in replies[j]["error"]
                   for j in ("old-unfused", "old-async"))
