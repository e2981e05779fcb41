# Reference Makefile:1-35 equivalents for the TPU build.
.PHONY: test tier1 chaos soak soak-smoke soak-regions replay-smoke proto certs docker release clean native

# Compile the C++ host runtime for the CURRENT source of
# gubernator_tpu/native/host_runtime.cpp.  Flags are pinned in ONE
# place (native.CXX_FLAGS) shared with the on-import rebuild, and the
# output is the hash-suffixed `_host_runtime_<sha256[:16]>.so` that
# tests/test_native_build.py requires to match the source in tier-1 —
# after editing the .cpp, run this and commit the fresh .so (deleting
# the superseded one).
native:
	python -c "from gubernator_tpu import native; print(native.build())"

# The whole suite on the virtual 8-device CPU mesh (conftest.py forces
# it); -p no:cacheprovider keeps runs hermetic like -count=1.
test:
	python -m pytest tests/ -q -p no:cacheprovider

# The ROADMAP verify command: fast deterministic tests only.  The
# metrics-name lint runs first (scripts/check_metrics_parity.py):
# reference-parity names are frozen, new names need review there.
tier1:
	env JAX_PLATFORMS=cpu python scripts/check_metrics_parity.py
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# Fault-injection suite (pytest.ini `chaos` marker): breaker /
# backoff / degraded-eval behavior under seeded fault plans, the
# resharding scenarios (owner death mid-transfer, DROP/DELAY on
# transfer frames, exactly-once oracle — tests/test_reshard_chaos.py),
# and the durability kill/restart recovery suite (SIGKILL a daemon
# mid-traffic and mid-snapshot-write, restart, assert monotone-bounded
# recovery — tests/test_snapshot_chaos.py), including the slow soaks
# tier-1 skips.
chaos:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m chaos \
		-p no:cacheprovider

# CPU-backend soak smoke: a short long_soak-derived run (slow-marked,
# excluded from tier-1) driving mixed traffic at a 2-daemon cluster
# while polling GET /debug/status and asserting steady-state
# invariants (healthy, breakers closed, no shed, occupancy
# monotone-consistent).  The one-command check of the saturation/SLO
# observability plane; scripts/cluster_status.py renders the same doc.
soak-smoke:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_soak_smoke.py -q \
		-m slow -p no:cacheprovider

# The full cluster soak (ROADMAP item 5's harness): 4 in-process
# daemons under seeded Zipf + burst-replay traffic with FaultPlan
# partitions and membership churn for minutes, trace-sampled, with the
# CONSERVATION AUDIT (audit.py) as the pass/fail gate — exits nonzero
# on any invariant violation (double-commit, lost hits, carry past the
# documented GLOBAL slack, negative remaining).
soak:
	env JAX_PLATFORMS=cpu python scripts/soak.py --minutes 3

# The 2x2 multi-region soak (ISSUE 11's acceptance topology): two
# 2-daemon regions (distinct GUBER_DATA_CENTER), MULTI_REGION lanes
# replicating cross-region through the federation plane
# (federation.py) with the inter-region wire under an always-on
# seeded WAN shape (FaultPlan latency/jitter/loss), WAN storms
# (effective partitions) injected and healed against one region at a
# time, and membership churn rotating WITHIN regions so each region
# reshards independently.  Same audit-silence gate as `make soak`,
# plus the region ledger must have moved (the plane demonstrably ran).
soak-regions:
	env JAX_PLATFORMS=cpu python scripts/soak.py --minutes 3 --regions 2x2

# Incident black box end-to-end in one command (architecture.md
# "Incident black box"): synthesize a capture with a duplicated
# forward frame, write a bundle, replay it TWICE against fresh
# daemons, and require byte-identical reports reproducing the
# forward_conservation violation.  Exits nonzero on any divergence.
replay-smoke:
	env JAX_PLATFORMS=cpu python scripts/replay.py --smoke

proto:
	bash scripts/proto.sh

docker:
	docker build -t gubernator-tpu:latest .

release:
	python -m build --wheel

# Self-signed cluster certs for the TLS compose file / tests
# (reference Makefile:21-34 openssl recipes).
certs:
	mkdir -p certs
	openssl req -x509 -newkey ec -pkeyopt ec_paramgen_curve:P-256 \
		-keyout certs/ca.key -out certs/ca.pem -days 3650 -nodes \
		-subj "/CN=gubernator-tpu CA"
	openssl req -newkey ec -pkeyopt ec_paramgen_curve:P-256 \
		-keyout certs/gubernator.key -out certs/gubernator.csr -nodes \
		-subj "/CN=gubernator"
	openssl x509 -req -in certs/gubernator.csr -CA certs/ca.pem \
		-CAkey certs/ca.key -CAcreateserial -out certs/gubernator.pem \
		-days 3650 \
		-extfile <(printf "subjectAltName=DNS:gubernator-1,DNS:gubernator-2,DNS:localhost,IP:127.0.0.1")
	rm -f certs/gubernator.csr certs/ca.srl

clean:
	rm -rf build dist *.egg-info .pytest_cache
	find . -name __pycache__ -type d -exec rm -rf {} +
