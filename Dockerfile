# Framework image: gateway, model server, and sidecar all run from this one
# image (the deploy/ manifests select the entrypoint via `command:`).
# Fills the reference Dockerfile's role (build the EPP binary, Dockerfile:1-20)
# for a Python+JAX runtime: g++/make stay in the image because the native
# scheduler rebuilds itself when its source changes, and libtpu comes from the
# jax[tpu] wheel.  Versions are intentionally floating in-repo; production
# builds should pin via a constraints file at build time
# (`pip install -c constraints.txt ...`) for reproducibility.
FROM python:3.12-slim AS base

RUN apt-get update && apt-get install -y --no-install-recommends \
        g++ make \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /srv/tpu-inference-gateway

# jax[tpu] pulls libtpu for GKE TPU node pools.
RUN pip install --no-cache-dir \
        "jax[tpu]" -f https://storage.googleapis.com/jax-releases/libtpu_releases.html \
        optax orbax-checkpoint aiohttp grpcio protobuf pyyaml jsonschema numpy

COPY llm_instance_gateway_tpu/ llm_instance_gateway_tpu/

# Pre-build the native scheduler so first pick isn't a compile.
RUN make -C llm_instance_gateway_tpu/native

ENV PYTHONPATH=/srv/tpu-inference-gateway
ENTRYPOINT ["python"]
CMD ["-m", "llm_instance_gateway_tpu.gateway.proxy", "--help"]
