#!/usr/bin/env bash
# Build the native image loader (libjpeg + libpng, no other deps).
# No -march=native: the artifact is the same wherever it is built. The
# compiler writes a private name and the result is moved into place, so a
# concurrent loader never maps a half-written library.
set -euo pipefail
cd "$(dirname "$0")"
tmp="libsparkdl_image.so.tmp.$$"
trap 'rm -f "$tmp"' EXIT
g++ -O3 -fPIC -shared -std=c++17 \
    image_loader.cc -o "$tmp" \
    -ljpeg -lpng -lpthread
mv -f "$tmp" libsparkdl_image.so
echo "built $(pwd)/libsparkdl_image.so"
