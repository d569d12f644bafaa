// Conformance decoder for tpuenc bitstreams, backed by the system libavcodec.
//
// The browser's WebCodecs VideoDecoder/ImageDecoder are the real consumers of
// the tpuenc H.264/JPEG output (reference client selkies-core.js:2032,2155,
// 2925-2968); bitstream bugs there present as silent black canvases.  This
// lib gives CI an equivalent oracle: decode our Annex-B / JFIF output with a
// production decoder and compare the pixels against the encoder's own
// reconstruction (H.264: must be bit-exact; JPEG: close to source).
//
// Built lazily by selkies_tpu.native.conformance_lib(); only used by tests
// and debug tooling, never on the streaming hot path.

extern "C" {
#include <libavcodec/avcodec.h>
#include <libavutil/imgutils.h>
}

#include <cstdint>
#include <cstring>

namespace {

struct Dec {
    const AVCodec *codec = nullptr;
    AVCodecContext *ctx = nullptr;
    AVFrame *frame = nullptr;
    AVPacket *pkt = nullptr;
};

Dec *dec_new(AVCodecID id) {
    const AVCodec *codec = avcodec_find_decoder(id);
    if (!codec) return nullptr;
    Dec *d = new Dec();
    d->codec = codec;
    d->ctx = avcodec_alloc_context3(codec);
    if (!d->ctx) { delete d; return nullptr; }
    // our streams have no reordering (poc type 2, no B-frames)
    d->ctx->flags |= AV_CODEC_FLAG_LOW_DELAY;
    if (avcodec_open2(d->ctx, codec, nullptr) < 0) {
        avcodec_free_context(&d->ctx);
        delete d;
        return nullptr;
    }
    d->frame = av_frame_alloc();
    d->pkt = av_packet_alloc();
    return d;
}

void dec_free(Dec *d) {
    if (!d) return;
    if (d->pkt) av_packet_free(&d->pkt);
    if (d->frame) av_frame_free(&d->frame);
    if (d->ctx) avcodec_free_context(&d->ctx);
    delete d;
}

// Copy one decoded frame's planes into tightly-packed caller buffers of
// y_cap / c_cap bytes.  Returns 0 on success, -6 if the frame exceeds the
// caller's capacity (never writes past it).
int copy_planes(const AVFrame *f, uint8_t *y, uint8_t *u, uint8_t *v,
                int64_t y_cap, int64_t c_cap, int *out_w, int *out_h) {
    const int w = f->width, h = f->height;
    *out_w = w;
    *out_h = h;
    const AVPixelFormat fmt = (AVPixelFormat)f->format;
    if (fmt != AV_PIX_FMT_YUV420P && fmt != AV_PIX_FMT_YUVJ420P)
        return -2;
    if ((int64_t)w * h > y_cap
        || (int64_t)((w + 1) / 2) * ((h + 1) / 2) > c_cap)
        return -6;
    for (int r = 0; r < h; ++r)
        memcpy(y + (size_t)r * w, f->data[0] + (size_t)r * f->linesize[0], w);
    const int cw = (w + 1) / 2, ch = (h + 1) / 2;
    for (int r = 0; r < ch; ++r) {
        memcpy(u + (size_t)r * cw, f->data[1] + (size_t)r * f->linesize[1], cw);
        memcpy(v + (size_t)r * cw, f->data[2] + (size_t)r * f->linesize[2], cw);
    }
    return 0;
}

}  // namespace

extern "C" {

void *conf_h264_new() { return dec_new(AV_CODEC_ID_H264); }
void *conf_mjpeg_new() { return dec_new(AV_CODEC_ID_MJPEG); }

void conf_dec_free(void *h) { dec_free((Dec *)h); }

// Feed one access unit (or a whole SPS+PPS+slice chunk); returns the number
// of frames decoded out (0 or 1 for our low-delay streams), negative on
// error.  On 1, the planes are written into y/u/v and dims into out_w/out_h.
int conf_dec_decode(void *h, const uint8_t *data, int64_t size,
                    uint8_t *y, uint8_t *u, uint8_t *v,
                    int64_t y_cap, int64_t c_cap,
                    int *out_w, int *out_h) {
    Dec *d = (Dec *)h;
    if (!d) return -1;
    // libavcodec requires input padding
    uint8_t *buf = (uint8_t *)av_malloc(size + AV_INPUT_BUFFER_PADDING_SIZE);
    if (!buf) return -1;
    memcpy(buf, data, size);
    memset(buf + size, 0, AV_INPUT_BUFFER_PADDING_SIZE);
    av_packet_unref(d->pkt);
    d->pkt->data = buf;
    d->pkt->size = (int)size;
    int rc = avcodec_send_packet(d->ctx, d->pkt);
    d->pkt->data = nullptr;
    d->pkt->size = 0;
    av_free(buf);
    if (rc < 0) return -3;
    int got = 0;
    while (true) {
        rc = avcodec_receive_frame(d->ctx, d->frame);
        if (rc == AVERROR(EAGAIN) || rc == AVERROR_EOF) break;
        if (rc < 0) return -4;
        int cp = copy_planes(d->frame, y, u, v, y_cap, c_cap, out_w, out_h);
        if (cp != 0) return cp == -6 ? -6 : -5;
        got += 1;
    }
    return got;
}

// Drain buffered frames at end of stream (harmless for low-delay streams).
int conf_dec_flush(void *h, uint8_t *y, uint8_t *u, uint8_t *v,
                   int64_t y_cap, int64_t c_cap,
                   int *out_w, int *out_h) {
    Dec *d = (Dec *)h;
    if (!d) return -1;
    if (avcodec_send_packet(d->ctx, nullptr) < 0) return -3;
    int got = 0;
    while (true) {
        int rc = avcodec_receive_frame(d->ctx, d->frame);
        if (rc == AVERROR(EAGAIN) || rc == AVERROR_EOF) break;
        if (rc < 0) return -4;
        int cp = copy_planes(d->frame, y, u, v, y_cap, c_cap, out_w, out_h);
        if (cp != 0) return cp == -6 ? -6 : -5;
        got += 1;
    }
    return got;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Reference x264 encoder (quality-gate tooling).
//
// The reference's daily driver is pixelflux's x264 at preset superfast with
// zerolatency tuning (reference gstwebrtc_app.py:609-640 x264enc
// speed-preset=superfast tune=zerolatency). tools/quality_measure.py encodes
// the same frames through THIS encoder and through tpuenc-H.264 and compares
// rate/distortion — the gate that decides whether deblocking/sub-pel/intra-4x4
// are worth building.  Tooling only, never on the streaming path.

namespace {

struct Enc {
    AVCodecContext *ctx = nullptr;
    AVFrame *frame = nullptr;
    AVPacket *pkt = nullptr;
    int64_t pts = 0;
};

void enc_free(Enc *e) {
    if (!e) return;
    if (e->pkt) av_packet_free(&e->pkt);
    if (e->frame) av_frame_free(&e->frame);
    if (e->ctx) avcodec_free_context(&e->ctx);
    delete e;
}

}  // namespace

extern "C" {

// crf >= 0 selects CRF rate control; bitrate_kbps > 0 selects ABR instead.
void *conf_x264_new(int w, int h, int crf, int bitrate_kbps,
                    const char *preset) {
    const AVCodec *codec = avcodec_find_encoder_by_name("libx264");
    if (!codec) return nullptr;
    Enc *e = new Enc();
    e->ctx = avcodec_alloc_context3(codec);
    if (!e->ctx) { delete e; return nullptr; }
    e->ctx->width = w;
    e->ctx->height = h;
    e->ctx->time_base = {1, 60};
    e->ctx->framerate = {60, 1};
    e->ctx->pix_fmt = AV_PIX_FMT_YUV420P;
    e->ctx->gop_size = 600;            // streaming posture: IDR then P's
    e->ctx->max_b_frames = 0;
    AVDictionary *opts = nullptr;
    av_dict_set(&opts, "preset", preset ? preset : "superfast", 0);
    av_dict_set(&opts, "tune", "zerolatency", 0);
    if (crf >= 0) {
        char buf[16];
        snprintf(buf, sizeof buf, "%d", crf);
        av_dict_set(&opts, "crf", buf, 0);
    } else if (bitrate_kbps > 0) {
        e->ctx->bit_rate = (int64_t)bitrate_kbps * 1000;
    }
    if (avcodec_open2(e->ctx, codec, &opts) < 0) {
        av_dict_free(&opts);
        enc_free(e);
        return nullptr;
    }
    av_dict_free(&opts);
    e->frame = av_frame_alloc();
    e->pkt = av_packet_alloc();
    e->frame->format = AV_PIX_FMT_YUV420P;
    e->frame->width = w;
    e->frame->height = h;
    if (av_frame_get_buffer(e->frame, 0) < 0) { enc_free(e); return nullptr; }
    return e;
}

void conf_enc_free(void *h) { enc_free((Enc *)h); }

// Encode one tightly-packed YUV420 frame; appends any produced packets to
// `out` (Annex-B) and returns bytes written (0 = buffered), negative on error.
int64_t conf_enc_encode(void *h, const uint8_t *y, const uint8_t *u,
                        const uint8_t *v, uint8_t *out, int64_t out_cap) {
    Enc *e = (Enc *)h;
    if (!e) return -1;
    if (av_frame_make_writable(e->frame) < 0) return -2;
    const int w = e->ctx->width, hgt = e->ctx->height;
    for (int r = 0; r < hgt; ++r)
        memcpy(e->frame->data[0] + (size_t)r * e->frame->linesize[0],
               y + (size_t)r * w, w);
    const int cw = (w + 1) / 2, ch = (hgt + 1) / 2;
    for (int r = 0; r < ch; ++r) {
        memcpy(e->frame->data[1] + (size_t)r * e->frame->linesize[1],
               u + (size_t)r * cw, cw);
        memcpy(e->frame->data[2] + (size_t)r * e->frame->linesize[2],
               v + (size_t)r * cw, cw);
    }
    e->frame->pts = e->pts++;
    if (avcodec_send_frame(e->ctx, e->frame) < 0) return -3;
    int64_t n = 0;
    while (true) {
        int rc = avcodec_receive_packet(e->ctx, e->pkt);
        if (rc == AVERROR(EAGAIN) || rc == AVERROR_EOF) break;
        if (rc < 0) return -4;
        if (n + e->pkt->size > out_cap) { av_packet_unref(e->pkt); return -6; }
        memcpy(out + n, e->pkt->data, e->pkt->size);
        n += e->pkt->size;
        av_packet_unref(e->pkt);
    }
    return n;
}

int64_t conf_enc_flush(void *h, uint8_t *out, int64_t out_cap) {
    Enc *e = (Enc *)h;
    if (!e) return -1;
    if (avcodec_send_frame(e->ctx, nullptr) < 0) return -3;
    int64_t n = 0;
    while (true) {
        int rc = avcodec_receive_packet(e->ctx, e->pkt);
        if (rc == AVERROR(EAGAIN) || rc == AVERROR_EOF) break;
        if (rc < 0) return -4;
        if (n + e->pkt->size > out_cap) { av_packet_unref(e->pkt); return -6; }
        memcpy(out + n, e->pkt->data, e->pkt->size);
        n += e->pkt->size;
        av_packet_unref(e->pkt);
    }
    return n;
}

}  // extern "C"
