"""Kubernetes provisioning offline: a recording fake kubectl shim.

Mirrors the reference's strategy of testing provisioning logic without a
cluster (reference: tests/unit_tests/kubernetes/).
"""

import json
import os
import stat
import textwrap

import pytest

from skypilot_tpu import exceptions
from skypilot_tpu.provision import kubernetes as k8s
from skypilot_tpu.provision.common import ProvisionConfig


@pytest.fixture()
def fake_kubectl(tmp_path, monkeypatch):
    """A shim that records argv+stdin and replays scripted pod JSON."""
    record = tmp_path / "calls.jsonl"
    pods_file = tmp_path / "pods.json"
    pods_file.write_text(json.dumps({"items": []}))
    svc_file = tmp_path / "svc.json"
    ing_file = tmp_path / "ingress.json"
    nodes_file = tmp_path / "nodes.json"
    nodes_file.write_text(json.dumps({"items": [
        {"status": {"addresses": [
            {"type": "InternalIP", "address": "10.9.0.1"},
            {"type": "ExternalIP", "address": "34.9.0.1"}]}}]}))
    shim = tmp_path / "kubectl"
    # -S skips site initialization (the shim is stdlib-only).
    shim.write_text(textwrap.dedent(f"""\
        #!/usr/bin/env -S python3 -S
        import json, os, sys
        stdin = sys.stdin.read() if not sys.stdin.isatty() else ""
        with open({str(record)!r}, "a") as f:
            f.write(json.dumps({{"argv": sys.argv[1:], "stdin": stdin}})
                    + "\\n")
        argv = sys.argv[1:]
        if argv[:2] == ["get", "pods"]:
            print(open({str(pods_file)!r}).read())
        elif argv[:2] == ["get", "nodes"]:
            print(open({str(nodes_file)!r}).read())
        elif argv[:2] == ["get", "service"]:
            if not os.path.exists({str(svc_file)!r}):
                print("not found", file=sys.stderr)
                sys.exit(1)
            print(open({str(svc_file)!r}).read())
        elif argv[:2] == ["get", "ingress"]:
            if not os.path.exists({str(ing_file)!r}):
                print("not found", file=sys.stderr)
                sys.exit(1)
            print(open({str(ing_file)!r}).read())
        elif argv[0] == "apply" and '"kind": "Service"' in stdin:
            # A minimal API server: NodePort Services get node ports
            # allocated; LoadBalancer Services get an external IP.
            svc = json.loads(stdin)
            if svc["spec"].get("type") == "NodePort":
                for i, p in enumerate(svc["spec"]["ports"]):
                    p.setdefault("nodePort", 30000 + i)
            if svc["spec"].get("type") == "LoadBalancer":
                svc["status"] = {{"loadBalancer": {{
                    "ingress": [{{"ip": "35.200.0.9"}}]}}}}
            with open({str(svc_file)!r}, "w") as f:
                json.dump(svc, f)
        elif argv[0] == "apply" and '"kind": "Ingress"' in stdin:
            ing = json.loads(stdin)
            ing["status"] = {{"loadBalancer": {{
                "ingress": [{{"ip": "34.120.0.7"}}]}}}}
            with open({str(ing_file)!r}, "w") as f:
                json.dump(ing, f)
        elif argv[:2] == ["delete", "service"]:
            if os.path.exists({str(svc_file)!r}):
                os.unlink({str(svc_file)!r})
        elif argv[:2] == ["delete", "ingress"]:
            if os.path.exists({str(ing_file)!r}):
                os.unlink({str(ing_file)!r})
        """))
    shim.chmod(shim.stat().st_mode | stat.S_IEXEC)
    monkeypatch.setenv("SKYTPU_KUBECTL", str(shim))

    class Ctl:
        def calls(self):
            if not record.exists():
                return []
            return [json.loads(l) for l in record.read_text().splitlines()]

        def set_pods(self, items):
            pods_file.write_text(json.dumps({"items": items}))

        def service(self):
            return (json.loads(svc_file.read_text())
                    if svc_file.exists() else None)

        def ingress(self):
            return (json.loads(ing_file.read_text())
                    if ing_file.exists() else None)

    return Ctl()


def _cfg(**kw):
    defaults = dict(cluster_name="kt", num_nodes=1, hosts_per_node=4,
                    zone="us-central2-b", region="us-central2",
                    accelerator="tpu-v5e-16", accelerator_count=16)
    defaults.update(kw)
    return ProvisionConfig(**defaults)


def _pod_item(name, node, worker, phase="Running", ip="10.0.0.1"):
    return {"metadata": {"name": name,
                         "labels": {k8s.LABEL: "kt",
                                    k8s.NODE_LABEL: str(node),
                                    k8s.WORKER_LABEL: str(worker)}},
            "status": {"phase": phase, "podIP": ip}}


def test_pod_manifest_tpu_selectors():
    spec = k8s.pod_manifest(_cfg(), node_id=0, worker_id=2)
    sel = spec["spec"]["nodeSelector"]
    assert sel["cloud.google.com/gke-tpu-accelerator"] == \
        "tpu-v5-lite-podslice"
    assert sel["cloud.google.com/gke-tpu-topology"] == "4x4"
    # 16 chips over 4 hosts -> 4 chips per pod.
    res = spec["spec"]["containers"][0]["resources"]
    assert res["limits"]["google.com/tpu"] == "4"
    assert spec["metadata"]["labels"][k8s.WORKER_LABEL] == "2"


def test_pod_manifest_spot_tolerations():
    spec = k8s.pod_manifest(_cfg(use_spot=True), 0, 0)
    assert spec["spec"]["nodeSelector"]["cloud.google.com/gke-spot"] == \
        "true"
    assert any(t["key"] == "cloud.google.com/gke-spot"
               for t in spec["spec"]["tolerations"])


def test_pod_manifest_unknown_topology():
    with pytest.raises(exceptions.ProvisionError):
        k8s.pod_manifest(_cfg(accelerator="tpu-v5e-12"), 0, 0)


def test_run_instances_applies_all_pods(fake_kubectl):
    rec = k8s.run_instances(_cfg())
    assert len(rec.created_instance_ids) == 4
    applies = [c for c in fake_kubectl.calls() if c["argv"][0] == "apply"]
    assert len(applies) == 4
    manifest = json.loads(applies[0]["stdin"])
    assert manifest["metadata"]["name"] == "kt-0-0"


def test_query_and_wait(fake_kubectl):
    assert k8s.query_instances("kt", "z") == "NOT_FOUND"
    fake_kubectl.set_pods([_pod_item("kt-0-0", 0, 0, "Pending")])
    assert k8s.query_instances("kt", "z") == "PARTIAL"
    fake_kubectl.set_pods([_pod_item("kt-0-0", 0, 0, "Running")])
    assert k8s.query_instances("kt", "z") == "UP"
    k8s.wait_instances("kt", "z", timeout=5)


def test_get_cluster_info_orders_hosts(fake_kubectl):
    fake_kubectl.set_pods([
        _pod_item("kt-0-1", 0, 1, ip="10.0.0.2"),
        _pod_item("kt-0-0", 0, 0, ip="10.0.0.1"),
    ])
    info = k8s.get_cluster_info("kt", "z")
    assert [h.worker_id for h in info.hosts] == [0, 1]
    assert info.hosts[0].internal_ip == "10.0.0.1"
    runners = k8s.get_command_runners(info)
    assert [r.pod_name for r in runners] == ["kt-0-0", "kt-0-1"]


def test_terminate_and_stop(fake_kubectl):
    k8s.terminate_instances("kt", "z")
    deletes = [c for c in fake_kubectl.calls()
               if c["argv"][0] == "delete"]
    # terminate removes the Service (port cleanup) AND the pods.
    assert any(f"{k8s.LABEL}=kt" in c["argv"] for c in deletes)
    assert any("service" in c["argv"] for c in deletes)
    with pytest.raises(exceptions.NotSupportedError):
        k8s.stop_instances("kt", "z")


def test_feature_negotiation_registry():
    """Reference parity: CloudImplementationFeatures (cloud.py:29) —
    capabilities are declared per provider, not rediscovered ad hoc."""
    from skypilot_tpu import provision
    from skypilot_tpu.provision import Feature
    assert not provision.supports("kubernetes", Feature.STOP)
    assert provision.supports("kubernetes", Feature.MULTI_NODE_EXEC)
    assert provision.supports("kubernetes",
                              Feature.HOST_CONTROLLERS)
    assert provision.supports("gcp", Feature.MULTI_NODE_EXEC)
    assert provision.supports("local", Feature.STOP)


# -- networking: NodePort Service exposure ----------------------------------

def test_ports_create_nodeport_service(fake_kubectl):
    k8s.run_instances(_cfg(ports=[8080, 9000]))
    svc = fake_kubectl.service()
    assert svc is not None
    assert svc["spec"]["type"] == "NodePort"
    assert svc["spec"]["selector"] == {
        k8s.LABEL: "kt", k8s.NODE_LABEL: "0", k8s.WORKER_LABEL: "0"}
    assert [p["port"] for p in svc["spec"]["ports"]] == [8080, 9000]


def test_query_ports_maps_node_address(fake_kubectl):
    k8s.run_instances(_cfg(ports=[8080]))
    eps = k8s.query_ports("kt")
    # The fake API allocates nodePort 30000; node ExternalIP preferred.
    assert eps == {8080: "34.9.0.1:30000"}


def test_dispatcher_query_ports(fake_kubectl):
    """provision.query_ports routes to the k8s provider; providers
    without port exposure answer {} without a provider call."""
    from skypilot_tpu import provision
    k8s.run_instances(_cfg(ports=[8080]))
    assert provision.query_ports("kubernetes", "kt") == \
        {8080: "34.9.0.1:30000"}
    assert provision.query_ports("local", "whatever") == {}


def test_terminate_cleans_up_service(fake_kubectl):
    k8s.run_instances(_cfg(ports=[8080]))
    assert fake_kubectl.service() is not None
    k8s.terminate_instances("kt", "us-central2-b")
    assert fake_kubectl.service() is None
    assert k8s.query_ports("kt") == {}


def test_no_service_without_ports(fake_kubectl):
    k8s.run_instances(_cfg())
    assert fake_kubectl.service() is None
    fake_kubectl.set_pods([_pod_item("kt-0-0", 0, 0)])
    info = k8s.get_cluster_info("kt", "us-central2-b")
    assert "port_endpoints" not in info.metadata


def test_port_forward_command(fake_kubectl):
    cmd = k8s.port_forward_command("kt", 8080, local_port=18080)
    assert "port-forward" in cmd
    assert "service/kt-skytpu-svc" in cmd
    assert "18080:8080" in cmd


def test_replica_url_prefers_port_endpoints(monkeypatch, tmp_path):
    """serve's replica URL uses the NodePort endpoint when the provider
    publishes one (pod IPs are cluster-internal)."""
    monkeypatch.setenv("SKYPILOT_TPU_HOME", str(tmp_path / "h"))
    from skypilot_tpu import provision
    from skypilot_tpu.serve import replica_managers
    from skypilot_tpu.serve.service_spec import SkyServiceSpec

    monkeypatch.setattr(
        provision, "query_ports",
        lambda provider, name: {8080: "34.9.0.1:30123"}
        if provider == "kubernetes" else {})
    spec = SkyServiceSpec.from_yaml_config({"readiness_probe": "/",
                                            "port": 8080, "replicas": 1})
    mgr = replica_managers.ReplicaManager(
        "s", spec, {"resources": {"cloud": "kubernetes"}})
    from skypilot_tpu.backend import ClusterHandle
    handle = ClusterHandle({"cluster_name": "c", "provider": "kubernetes",
                            "zone": "z"})
    assert mgr._replica_url(handle, 1) == "http://34.9.0.1:30123"


def test_replica_port_override_normalizes_forms():
    """The schema allows ports as string/scalar forms; the replica
    override must not crash on them (a TypeError here silently FAILs
    every replica)."""
    from skypilot_tpu.serve.replica_managers import \
        _apply_resource_overrides
    for raw in (["8080"], "8080", 8080, None, [8080, "8081"]):
        cfg = _apply_resource_overrides(
            {"resources": {"cloud": "local", "ports": raw}},
            use_spot=None, port=9001)
        ports = cfg["resources"]["ports"]
        assert 9001 in ports
        assert all(isinstance(p, int) for p in ports)
    # List-of-resources form + spot override compose.
    cfg = _apply_resource_overrides(
        {"resources": [{"cloud": "local"}, {"cloud": "gcp"}]},
        use_spot=True, port=8080)
    assert all(r["use_spot"] and r["ports"] == [8080]
               for r in cfg["resources"])


# -- GPU-on-k8s + ingress/LoadBalancer exposure (VERDICT r3 #9) --------------

def test_pod_manifest_gpu_selectors():
    cfg = _cfg(accelerator="A100", accelerator_count=8)
    spec = k8s.pod_manifest(cfg, 0, 0)
    sel = spec["spec"]["nodeSelector"]
    assert sel["cloud.google.com/gke-accelerator"] == "nvidia-tesla-a100"
    res = spec["spec"]["containers"][0]["resources"]
    assert res["requests"]["nvidia.com/gpu"] == "8"
    assert res["limits"]["nvidia.com/gpu"] == "8"
    assert any(t["key"] == "nvidia.com/gpu"
               for t in spec["spec"]["tolerations"])


def test_pod_manifest_unknown_gpu():
    with pytest.raises(exceptions.ProvisionError):
        k8s.pod_manifest(_cfg(accelerator="RTX9999",
                              accelerator_count=1), 0, 0)


def test_pod_manifest_gpu_spot():
    spec = k8s.pod_manifest(_cfg(accelerator="A100",
                                 accelerator_count=1,
                                 use_spot=True), 0, 0)
    assert spec["spec"]["nodeSelector"][
        "cloud.google.com/gke-spot"] == "true"
    assert any(t["key"] == "cloud.google.com/gke-spot"
               for t in spec["spec"]["tolerations"])


def test_pod_manifest_docker_image_id():
    """docker:<img> on k8s: the pod IS the container — the bare image
    becomes the pod image (not the literal 'docker:...' reference)."""
    spec = k8s.pod_manifest(_cfg(image_id="docker:myorg/env:7"), 0, 0)
    assert spec["spec"]["containers"][0]["image"] == "myorg/env:7"
    # Plain image ids pass through untouched.
    spec = k8s.pod_manifest(_cfg(image_id="ubuntu:22.04"), 0, 0)
    assert spec["spec"]["containers"][0]["image"] == "ubuntu:22.04"


def test_loadbalancer_mode(fake_kubectl):
    from skypilot_tpu import config as config_lib
    with config_lib.replace_config({"kubernetes":
                                    {"ports": "loadbalancer"}}):
        k8s.open_ports("kt", [8080, 9090])
        svc = fake_kubectl.service()
        assert svc["spec"]["type"] == "LoadBalancer"
        eps = k8s.query_ports("kt")
    assert eps == {8080: "35.200.0.9:8080", 9090: "35.200.0.9:9090"}


def test_ingress_mode_endpoints(fake_kubectl):
    from skypilot_tpu import config as config_lib
    with config_lib.replace_config({"kubernetes": {"ports": "ingress"}}):
        k8s.open_ports("kt", [8080])
        svc = fake_kubectl.service()
        assert svc["spec"]["type"] == "ClusterIP"
        ing = fake_kubectl.ingress()
        path = ing["spec"]["rules"][0]["http"]["paths"][0]
        assert path["backend"]["service"]["port"]["number"] == 8080
        assert "/skytpu/kt/8080" in path["path"]
        eps = k8s.query_ports("kt")
    # Ingress endpoints are path-based and flow into query_ports the
    # way NodePort endpoints do (usable as http://{endpoint}).
    assert eps == {8080: "34.120.0.7/skytpu/kt/8080"}
    k8s.cleanup_ports("kt")
    assert fake_kubectl.ingress() is None


def test_bad_ports_mode_rejected():
    from skypilot_tpu import config as config_lib
    with config_lib.replace_config({"kubernetes": {"ports": "magic"}}):
        with pytest.raises(exceptions.ProvisionError):
            k8s.ports_mode()
