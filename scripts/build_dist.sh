#!/bin/sh
# Distribution zip (equivalent of the reference's build.sh assembly zip:
# build.sh:6-16 bundles the spark jar + web jar; here one zip carries the
# python package, the native featurizer source, and the dashboard assets).
set -e
version="0.1.0"
cd "$(dirname "$0")/.."
rm -rf target && mkdir -p target
# minify dashboard assets (the reference's sbt-uglify step, web/build.sbt:25-39);
# the server serves file.min.js when present (web/server.py)
python tools/jsminify.py twtml_tpu/web/assets/js/api.js \
    twtml_tpu/web/assets/js/index.js twtml_tpu/web/assets/js/chart.js \
    twtml_tpu/web/assets/js/test.js
zip -qr "target/twtml-tpu-${version}.zip" \
    twtml_tpu native pyproject.toml README.md LICENSE \
    -x "*/__pycache__/*" -x "*.so"
echo "target/twtml-tpu-${version}.zip"
