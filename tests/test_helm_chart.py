"""Golden rendering checks for deploy/charts/cerbos-tpu.

``helm template`` is driven over three values variants (defaults, TLS,
policies-from-ConfigMap + engine overrides) and the rendered manifests are
asserted structurally. Skips cleanly when helm is not installed; the static
chart checks at the bottom run regardless.
"""

import os
import shutil
import subprocess

import pytest
import yaml

CHART_DIR = os.path.join(
    os.path.dirname(__file__), "..", "deploy", "charts", "cerbos-tpu"
)

HELM = shutil.which("helm")


def render(*set_args):
    cmd = [HELM, "template", "pdp", CHART_DIR]
    for s in set_args:
        cmd += ["--set", s]
    out = subprocess.run(cmd, capture_output=True, text=True, check=True).stdout
    docs = [d for d in yaml.safe_load_all(out) if d]
    return {(d["kind"], d["metadata"]["name"]): d for d in docs}


def container(deployment):
    return deployment["spec"]["template"]["spec"]["containers"][0]


@pytest.mark.skipif(HELM is None, reason="helm not installed")
class TestHelmTemplate:
    def test_default_values(self):
        docs = render()
        assert set(docs) == {
            ("Deployment", "pdp-cerbos-tpu"),
            ("Service", "pdp-cerbos-tpu"),
            ("ConfigMap", "pdp-cerbos-tpu-config"),
        }
        dep = docs[("Deployment", "pdp-cerbos-tpu")]
        c = container(dep)
        assert c["image"] == "cerbos-tpu:latest"
        assert c["args"] == ["server", "--config", "/config/config.yaml"]
        # config rollouts restart pods: the checksum annotation must exist
        ann = dep["spec"]["template"]["metadata"]["annotations"]
        assert len(ann["checksum/config"]) == 64
        # probes stay plain HTTP without TLS
        assert "scheme" not in c["livenessProbe"]["httpGet"]
        # readiness is warmup-gated and split from liveness
        assert c["livenessProbe"]["httpGet"]["path"] == "/_cerbos/health"
        assert c["readinessProbe"]["httpGet"]["path"] == "/_cerbos/ready"
        # the rendered config carries the serving-path knobs end to end
        conf = yaml.safe_load(
            docs[("ConfigMap", "pdp-cerbos-tpu-config")]["data"]["config.yaml"]
        )
        tpu = conf["engine"]["tpu"]
        assert tpu["enabled"] is True
        assert tpu["inflightDepth"] == 3
        assert tpu["pipelineChunk"] == 4096
        # device-path fault domain defaults (docs/ROBUSTNESS.md)
        assert tpu["breaker"]["enabled"] is True
        assert tpu["breaker"]["failureThreshold"] == 5
        assert tpu["quarantineMax"] == 128
        assert "tls" not in conf.get("server", {})
        svc = docs[("Service", "pdp-cerbos-tpu")]
        assert {(p["name"], p["port"]) for p in svc["spec"]["ports"]} == {
            ("http", 3592),
            ("grpc", 3593),
        }

    def test_tls_variant(self):
        docs = render("tls.secretName=pdp-tls")
        dep = docs[("Deployment", "pdp-cerbos-tpu")]
        c = container(dep)
        assert c["livenessProbe"]["httpGet"]["scheme"] == "HTTPS"
        assert c["readinessProbe"]["httpGet"]["scheme"] == "HTTPS"
        vols = {v["name"]: v for v in dep["spec"]["template"]["spec"]["volumes"]}
        assert vols["tls"]["secret"]["secretName"] == "pdp-tls"
        assert {"name": "tls", "mountPath": "/tls"} in c["volumeMounts"]
        conf = yaml.safe_load(
            docs[("ConfigMap", "pdp-cerbos-tpu-config")]["data"]["config.yaml"]
        )
        assert conf["server"]["tls"] == {"cert": "/tls/tls.crt", "key": "/tls/tls.key"}

    def test_policies_configmap_and_engine_overrides(self):
        docs = render(
            "policies.configMapName=my-policies",
            "cerbos.config.engine.tpu.inflightDepth=2",
            "cerbos.config.engine.tpu.pipelineChunk=512",
        )
        dep = docs[("Deployment", "pdp-cerbos-tpu")]
        vols = {v["name"]: v for v in dep["spec"]["template"]["spec"]["volumes"]}
        assert vols["policies"]["configMap"]["name"] == "my-policies"
        assert {"name": "policies", "mountPath": "/policies"} in container(dep)[
            "volumeMounts"
        ]
        conf = yaml.safe_load(
            docs[("ConfigMap", "pdp-cerbos-tpu-config")]["data"]["config.yaml"]
        )
        assert conf["engine"]["tpu"]["inflightDepth"] == 2
        assert conf["engine"]["tpu"]["pipelineChunk"] == 512


class TestChartStatic:
    """Checks that hold without helm installed."""

    def test_chart_metadata(self):
        with open(os.path.join(CHART_DIR, "Chart.yaml"), encoding="utf-8") as f:
            chart = yaml.safe_load(f)
        assert chart["name"] == "cerbos-tpu"
        assert chart["apiVersion"] == "v2"

    def test_default_values_parse_and_match_engine_defaults(self):
        with open(os.path.join(CHART_DIR, "values.yaml"), encoding="utf-8") as f:
            values = yaml.safe_load(f)
        tpu = values["cerbos"]["config"]["engine"]["tpu"]
        from cerbos_tpu.config import DEFAULTS

        want = DEFAULTS["engine"]["tpu"]
        for knob in ("inflightDepth", "pipelineChunk", "quarantineMax"):
            assert tpu[knob] == want[knob], knob
        for knob in ("enabled", "failureThreshold", "probeBackoffBaseMs", "probeBackoffCapMs"):
            assert tpu["breaker"][knob] == want["breaker"][knob], knob
        for knob in ("enabled", "capacity"):
            assert tpu["flightRecorder"][knob] == want["flightRecorder"][knob], knob
        for knob in ("enabled", "batchSizes", "background", "timeoutSeconds"):
            assert tpu["warmup"][knob] == want["warmup"][knob], knob
        for knob in ("enabled", "maxArtifacts", "maxSeconds"):
            assert tpu["profiler"][knob] == want["profiler"][knob], knob
        for knob in ("enabled", "slowRingCapacity", "slowThresholdMs"):
            assert tpu["latencyBudget"][knob] == want["latencyBudget"][knob], knob
        for knob in ("enabled", "intervalMs", "windowSec"):
            assert tpu["pressure"][knob] == want["pressure"][knob], knob
        for knob in ("socketPath", "transport", "ringKiB", "requestTimeoutMs", "maxOutstanding"):
            assert tpu["sharedBatcher"][knob] == want["sharedBatcher"][knob], knob
        # overload control block (docs/ROBUSTNESS.md, "Overload & brownout")
        overload = values["cerbos"]["config"]["overload"]
        want_ov = DEFAULTS["overload"]
        assert overload["enabled"] == want_ov["enabled"]
        assert overload["classes"] == want_ov["classes"]
        for knob in ("enabled", "hysteresis", "holdSeconds", "stages"):
            assert overload["brownout"][knob] == want_ov["brownout"][knob], knob

    def test_readiness_probe_split_from_liveness(self):
        # a cold replica must not take traffic until warmup has compiled the
        # expected device layouts; liveness stays on the plain health endpoint
        with open(
            os.path.join(CHART_DIR, "templates", "deployment.yaml"), encoding="utf-8"
        ) as f:
            tpl = f.read()
        assert "/_cerbos/ready" in tpl
        assert "/_cerbos/health" in tpl

    def test_prometheus_scrape_annotations(self):
        with open(os.path.join(CHART_DIR, "values.yaml"), encoding="utf-8") as f:
            values = yaml.safe_load(f)
        assert values["metrics"] == {"scrape": True, "path": "/_cerbos/metrics"}
        with open(
            os.path.join(CHART_DIR, "templates", "deployment.yaml"), encoding="utf-8"
        ) as f:
            tpl = f.read()
        for ann in ("prometheus.io/scrape", "prometheus.io/path", "prometheus.io/port"):
            assert ann in tpl, ann

    def test_grafana_dashboard_parses_and_targets_registry_metrics(self):
        import json
        import re

        path = os.path.join(os.path.dirname(CHART_DIR), "..", "grafana-dashboard.json")
        with open(path, encoding="utf-8") as f:
            dash = json.load(f)
        assert dash["panels"], "dashboard has no panels"
        exprs = [t["expr"] for p in dash["panels"] for t in p.get("targets", [])]
        assert exprs
        # every metric the dashboard queries must follow the naming scheme
        for name in re.findall(r"cerbos_tpu_[a-z0-9_]+", " ".join(exprs)):
            assert re.fullmatch(r"cerbos_tpu_[a-z0-9_]+", name)
        joined = " ".join(exprs)
        for needle in (
            "cerbos_tpu_batch_stage_seconds_bucket",
            "cerbos_tpu_batch_occupancy",
            "cerbos_tpu_breaker_state",
            "cerbos_tpu_breaker_transitions_total",
            "cerbos_tpu_xla_compile_seconds_bucket",
            "cerbos_tpu_xla_compiles_total",
            "cerbos_tpu_recompile_storms_total",
            "cerbos_tpu_xla_layout_cardinality",
            "cerbos_tpu_device_memory_bytes_in_use",
            "cerbos_tpu_readiness_state",
            # latency budget & pressure row (PR 9)
            "cerbos_tpu_request_stage_seconds_bucket",
            "cerbos_tpu_deadline_budget_remaining_seconds_bucket",
            "cerbos_tpu_decisions_total",
            "cerbos_tpu_pressure_score",
            # IPC transport row (PR 10)
            "cerbos_tpu_ipc_ring_depth",
            "cerbos_tpu_ipc_full_total",
            "cerbos_tpu_ipc_frame_bytes_bucket",
            "cerbos_tpu_ipc_client_rtt_seconds_bucket",
            # overload row (admission + brownout)
            "cerbos_tpu_admission_total",
            "cerbos_tpu_admission_inflight",
            "cerbos_tpu_admission_refusal_seconds_bucket",
            "cerbos_tpu_admission_queue_budget_total",
            "cerbos_tpu_brownout_stage",
            "cerbos_tpu_brownout_shed_total",
            "cerbos_tpu_brownout_transitions_total",
            # plan row (batched PlanResources)
            "cerbos_tpu_plan_batch_seconds_bucket",
            "cerbos_tpu_plan_queries_total",
            "cerbos_tpu_plan_residual_rules_bucket",
            "cerbos_tpu_plan_parity_checks_total",
            "cerbos_tpu_plan_parity_divergence_total",
            # provenance row (decision attribution + hot rules)
            "cerbos_tpu_rule_hits_total",
            "cerbos_tpu_decision_source_total",
        ):
            assert needle in joined, needle

    def test_all_templates_present(self):
        tdir = os.path.join(CHART_DIR, "templates")
        assert {
            "deployment.yaml",
            "service.yaml",
            "configmap.yaml",
            "_helpers.tpl",
        } <= set(os.listdir(tdir))
