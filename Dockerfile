# jylis-tpu container image (reference analog: /root/reference/Dockerfile's
# two-stage build — compile in a full toolchain image, ship a minimal
# runtime; the Pony static-binary-in-scratch trick has no Python
# equivalent, so the runtime stage is a slim Python base instead).
#
# CPU image by default (jax[cpu]): a single node, or a docker-compose
# cluster (docker-compose.yml), runs anywhere. For TPU serving, build with
#   --build-arg JAX_EXTRA="jax[tpu] -f https://storage.googleapis.com/jax-releases/libtpu_releases.html"
# on a TPU VM base, or install the image's wheel into your TPU runtime.

FROM python:3.11-slim AS build
RUN apt-get update && apt-get install -y --no-install-recommends g++ \
    && rm -rf /var/lib/apt/lists/*
WORKDIR /src
COPY native/ native/
# the native codecs (RESP scanner, cluster codec, counter engine): one
# shared object, no Python build step needed
RUN g++ -O2 -std=c++17 -shared -fPIC -pthread -o native/libjylis_native.so native/*.cpp

FROM python:3.11-slim
ARG JAX_EXTRA="jax[cpu]"
RUN pip install --no-cache-dir ${JAX_EXTRA} numpy
WORKDIR /app
COPY jylis_tpu/ jylis_tpu/
COPY LICENSE .
COPY --from=build /src/native/libjylis_native.so jylis_tpu/native/
LABEL org.opencontainers.image.title="jylis-tpu" \
      org.opencontainers.image.licenses="MPL-2.0"
ENV JYLIS_NATIVE_SO=/app/jylis_tpu/native/libjylis_native.so
# RESP port (same default as Redis and the reference) + cluster port
EXPOSE 6379 9999
ENTRYPOINT ["python", "-m", "jylis_tpu"]
